"""The stage readers (``fed_scopes.py`` and the nine metrics on it) and
``tools/scope_table.py`` on recorded traces: two crops of the fused cell's
trace with the program's ``fed.*`` scopes in it, and the older crop of a
program without them."""

import copy
import os
import types

import numpy as np
import pytest

from benchmark import cells, fed_scopes, trace_reduce
from benchmark.tools import scope_table

TESTDATA = os.path.join(cells.ROOT, "testdata")
UNSCOPED = "gpt2l_silo_fused_v5e_30ms"
# across the end of a round: last backward, optimizer, stack write,
# aggregation; and across a step's turn from forward to backward
SCOPED = ["gpt2l_silo_fused_v5e_scoped_round_end",
          "gpt2l_silo_fused_v5e_scoped_fwd_bwd"]
COVERAGE = "fed_scope_coverage_pct"
PARTITION = ["forward_pct", "backward_pct", "optimizer_pct",
             "step_overhead_pct", "client_stack_pct", "aggregate_pct"]
CUTS = ["head_loss_pct", "model_elementwise_pct"]
# the program's scopes that are in no bucket until a cell runs them
UNREAD = {"fed.codec", "fed.agg_transform"}


@pytest.fixture(scope="module")
def contexts():
    """What a stage reader is handed, by trace: they read the summary only."""
    return {name: types.SimpleNamespace(summary=trace_reduce.reduce_trace(
        os.path.join(TESTDATA, f"{name}.textproto")))
        for name in SCOPED + [UNSCOPED]}


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


@pytest.mark.parametrize("trace", SCOPED)
@pytest.mark.parametrize("name", [COVERAGE] + PARTITION + CUTS)
def test_reader_gives_a_finite_number_on_a_scoped_trace(contexts, name, trace):
    value = read(name, contexts[trace])
    assert value is not None and np.isfinite(value) and value >= 0


@pytest.mark.parametrize("trace", SCOPED)
def test_the_stages_partition_busy_time(contexts, trace):
    ctx = contexts[trace]
    stages = sum(read(name, ctx) for name in PARTITION)
    assert stages + (100 - read(COVERAGE, ctx)) == pytest.approx(100, abs=1e-6)
    assert read(COVERAGE, ctx) > 90
    # the cuts across the partition stay inside what they cut
    assert read("head_loss_pct", ctx) <= (
        read("forward_pct", ctx) + read("backward_pct", ctx))
    assert read("model_elementwise_pct", ctx) <= (
        read("forward_pct", ctx) + read("backward_pct", ctx))


@pytest.mark.parametrize("name", PARTITION + CUTS)
def test_the_recorded_traces_hold_an_op_of_every_bucket(contexts, name):
    assert max(read(name, contexts[t]) for t in SCOPED) > 0


def test_the_recorded_traces_hold_an_op_without_a_scope(contexts):
    assert min(read(COVERAGE, contexts[t]) for t in SCOPED) < 100


@pytest.mark.parametrize("tf_op,scope,backward", [
    ("jit(multi_round_fn)/fed.rounds/while/body/closed_call/fed.round/"
     "fed.clients/while/body/closed_call/fed.local_update/while/body/"
     "closed_call/fed.step/jvp(fed.model)/dot_general", "fed.model", False),
    ("a/fed.step/transpose(jvp(fed.model))/dot_general", "fed.model", True),
    ("a/fed.step/transpose(jvp(fed.loss))/mul", "fed.loss", True),
    ("a/fed.step/fed.optimizer/sub", "fed.optimizer", False),
    ("jit(multi_round_fn)/while/body/closed_call:", None, False),
    ("", None, False),
])
def test_innermost_scope_and_direction_of_a_name(tf_op, scope, backward):
    op = trace_reduce.Op("x", 0.0, 1.0, stats={"tf_op": tf_op})
    assert fed_scopes.innermost(op) == scope
    assert fed_scopes.is_backward(op) == backward


@pytest.mark.parametrize("name", PARTITION + CUTS)
def test_a_stage_reader_says_nothing_on_a_trace_without_scopes(contexts, name):
    assert read(name, contexts[UNSCOPED]) is None


def test_coverage_is_zero_on_a_trace_without_scopes(contexts):
    assert read(COVERAGE, contexts[UNSCOPED]) == 0


def test_every_scope_a_reader_names_is_the_programs():
    from fedml_tpu.obs.scopes import SCOPES

    named = {n: set(cells.load_layer_metric(n).SCOPES)
             for n in PARTITION + CUTS}
    for name, scopes in named.items():
        assert scopes <= set(SCOPES), name
    # the buckets are disjoint but for forward/backward, which the
    # direction splits, and with the unread two they are all there is
    buckets = [named[n] for n in PARTITION if n != "backward_pct"]
    assert sum(len(b) for b in buckets) == len(set().union(*buckets))
    assert named["forward_pct"] == named["backward_pct"]
    assert set().union(*buckets) | UNREAD == set(SCOPES)
    assert all(fed_scopes.SCOPE.fullmatch(s) for s in SCOPES)


def test_scope_table_sums_to_the_trace_and_takes_either_annotation():
    path = os.path.join(TESTDATA, f"{SCOPED[0]}.textproto")
    profile = trace_reduce.load_profile(path)
    rules = scope_table.rules_for(profile)
    assert rules == trace_reduce.load_rules()
    summary = trace_reduce.reduce_profile(profile, rules)
    rows, uncovered = scope_table.table(summary)
    total = summary.seconds_where(lambda op: True)
    assert sum(rows.values()) + sum(uncovered.values()) == pytest.approx(total)
    assert ("fed.optimizer", "", "other") in rows
    assert any(k[0] == "fed.model" and k[1] == "bwd" and k[2] == "matmul"
               for k in rows)
    # the same trace as ``obs/jax_hooks.trace_rounds`` would have marked it
    renamed = copy.deepcopy(profile)
    for plane in renamed:
        for line in plane.lines:
            for e in line.events:
                if e.name == rules["call_annotation"]:
                    e.name = scope_table.ROUND_ANNOTATION
    other = scope_table.rules_for(renamed)
    assert other["call_annotation"] == scope_table.ROUND_ANNOTATION
    again = scope_table.table(trace_reduce.reduce_profile(renamed, other))
    assert again == (rows, uncovered)
