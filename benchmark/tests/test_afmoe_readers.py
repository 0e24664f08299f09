"""The readers the ``trinitymini_silo_chat8k`` cell brought, on 50 ms cropped
from the builder's own trace of the cell on a v5e (PR 41;
``tools/crop_trace.py <trace> <out> 45 50``: a step's turn from forward to
backward: the full layer's and the last sliding layer's attention with their
gates, norms and expert layers, the head, and into the last layer's
backward), with a stand-in session and the counter of one step; and on the
other families' traces, where each finds nothing."""

import os
import re
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

TESTDATA = os.path.join(cells.ROOT, "testdata")
CELL = "trinitymini_silo_chat8k"
TURN = os.path.join(TESTDATA, CELL, "v5e_turn.textproto")
MELLUM = os.path.join(TESTDATA, "mellum2_silo_code8k", "v5e_step.textproto")
KIMI = os.path.join(TESTDATA, "kimilin_silo_doc8k", "v5e_turn.textproto")
KEYE = os.path.join(TESTDATA, "keyevl2_silo_text8k", "v5e_turn.textproto")
GPT2 = os.path.join(TESTDATA, "gpt2l_silo_fused_v5e_30ms.textproto")
NEW = ["attn_gate_pct", "attn_gate_roofline", "norm_pct",
       "router_bias_moved_pct"]
MOVED = 8192.0  # of one step's 8192 tokens x 4 expert layers: a quarter


def context(trace, cell_name, metrics, samples=1):
    call = (0.0, 0.1, 1, {k: np.array([v]) for k, v in metrics.items()})
    return trace_reduce.Context(
        summary=trace_reduce.reduce_trace(trace),
        cell=cells.load_cell(cell_name),
        session=types.SimpleNamespace(
            padded_samples_per_round=lambda: samples),
        calls=[call], device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def ctx():
    return context(TURN, CELL, {"count": 8192.0,
                                "moe_tokens_bias_moved": MOVED})


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


def seconds(ctx, scope, *needles, klass=None):
    """Self seconds of the ops whose last ``model.*`` segment is ``scope``
    and whose ``tf_op`` holds every needle."""
    def keep(op):
        name = str(op.stats.get("tf_op", ""))
        parts = re.findall(r"model\.[a-z_]+", name)
        return bool(parts) and parts[-1] == scope and all(
            n in name for n in needles) and klass in (None, op.klass)

    return ctx.summary.seconds_where(keep)


def test_the_manifest_reads_them_in_this_cell_only():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tokens_per_s"
        assert by_name[name]["unit"] == "%"
    assert [by_name[n]["source"] for n in NEW] == [
        "device_trace", "device_trace", "device_trace", "program_counter"]
    assert [by_name[n]["layer"] for n in NEW] == [
        "model step", "kernels", "model step", "model step"]
    assert [by_name[n]["better"] for n in NEW] == [
        "lower", "higher", "lower", "lower"]
    # appended: the entries that were there stand where they stood
    names = [m["name"] for m in cells.manifest()["per_layer"]]
    assert names[-4:] == NEW
    entry = cells.manifest()["workloads"][-1]
    assert (entry["name"], entry["chips"], entry["traffic"]) == (
        CELL, 1, "silo_chat8k")
    assert cells.manifest()["configs"][-1]["reduced"] == [
        "n_layer", "num_dense_layers", "num_experts", "vocab_size"]


def test_the_scopes_and_the_counter_a_reader_names_are_the_programs():
    from fedml_tpu.models.decoder import TOKENS_BIAS_MOVED
    from fedml_tpu.obs import scopes

    assert cells.load_layer_metric("attn_gate_pct").SCOPE \
        == cells.load_layer_metric("attn_gate_roofline").SCOPE \
        == scopes.ATTN_GATE
    assert cells.load_layer_metric("norm_pct").SCOPE == scopes.NORM
    assert cells.load_layer_metric("router_bias_moved_pct").COUNTER \
        == TOKENS_BIAS_MOVED


def test_the_gates_share_is_its_scopes_seconds_both_ways(ctx):
    gate = seconds(ctx, "model.attn_gate")
    assert 0 < gate < ctx.summary.busy_s
    assert read("attn_gate_pct", ctx) == pytest.approx(
        100 * gate / ctx.summary.busy_s)
    # the projection forward and backward, the sigmoid and the multiply
    assert seconds(ctx, "model.attn_gate", "gate/dot_general",
                   klass="matmul") > 0
    assert seconds(ctx, "model.attn_gate", "transpose(") > 0
    assert seconds(ctx, "model.attn_gate", klass="other") > 0
    # the gate runs outside the attention module's vmap: none of it is in
    # the trace's attention class, which stays the attention function's
    assert ctx.summary.seconds_where(
        lambda op: op.klass == "attention" and "model.attn_gate" in str(
            op.stats.get("tf_op", ""))) == 0
    assert seconds(ctx, "model.attn_sliding", klass="attention") > 0
    assert seconds(ctx, "model.attn_full", klass="attention") > 0


def test_the_gates_roofline_credits_its_product_over_its_matmul_seconds(ctx):
    product = seconds(ctx, "model.attn_gate", klass="matmul")
    # 2048 -> 4096 in each of five layers, three passes, one step's tokens
    flops = 3 * 5 * 2 * 2048 * 4096 * 8192
    got = read("attn_gate_roofline", ctx)
    assert got == pytest.approx(100 * flops / 197e12 / product)
    # the same seconds over twice the sequences read twice the share
    twice = context(TURN, CELL, {"count": 16384.0}, samples=2)
    assert read("attn_gate_roofline", twice) == pytest.approx(2 * got)
    # no product runs over its roofline: the longest op of the class under
    # the scope (one layer's product, whole inside the crop) takes no less
    # than one product's least time (the crop holds a few layers' products
    # of a step's fifteen, so the share above is not the cell's)
    longest = max(op.self_ns / 1e9 for d in ctx.summary.devices
                  for op in d.ops if op.klass == "matmul"
                  and "model.attn_gate" in str(op.stats.get("tf_op", "")))
    assert longest >= 2 * 2048 * 4096 * 8192 / 197e12


def test_the_norms_share_is_what_keeps_the_name(ctx):
    norms = seconds(ctx, "model.norm")
    assert 0 < norms < ctx.summary.busy_s
    assert read("norm_pct", ctx) == pytest.approx(
        100 * norms / ctx.summary.busy_s)
    # the norms after the sublayers are under the name, forward and backward
    assert seconds(ctx, "model.norm", "post_attn_norm") > 0
    assert seconds(ctx, "model.norm", "post_mlp_norm") > 0
    assert seconds(ctx, "model.norm", "transpose(") > 0


@pytest.mark.parametrize("moved, want", [(MOVED, 25.0), (0.0, 0.0),
                                         (4 * 8192.0, 100.0)])
def test_tokens_moved_is_the_counter_over_tokens_and_expert_layers(moved,
                                                                   want):
    got = read("router_bias_moved_pct", context(
        TURN, CELL, {"count": 8192.0, "moe_tokens_bias_moved": moved}))
    assert got == pytest.approx(want)


def test_partition_identity_holds_in_the_new_cell(ctx):
    stages = ("forward_pct", "backward_pct", "optimizer_pct",
              "step_overhead_pct", "client_stack_pct", "aggregate_pct")
    total = sum(read(n, ctx) or 0.0 for n in stages)
    assert total + 100 - read("fed_scope_coverage_pct", ctx) \
        == pytest.approx(100, abs=1e-6)
    assert read("forward_pct", ctx) + read("backward_pct", ctx) > 80


@pytest.mark.parametrize("name", ["matmul_roofline", "step_mfu_pct",
                                  "attention_pct", "matmul_pct",
                                  "attn_gate_pct", "norm_pct"])
def test_the_shares_stay_under_100_here(ctx, name):
    part = 50.0 / 186.0  # the crop's share of a step
    whole_step = context(TURN, CELL, {"count": 8192.0 * part}, samples=part)
    assert 0 < read(name, whole_step) < 100


@pytest.mark.parametrize("trace, cell", [(GPT2, "gpt2l_silo_fused"),
                                         (MELLUM, "mellum2_silo_code8k"),
                                         (KIMI, "kimilin_silo_doc8k"),
                                         (KEYE, "keyevl2_silo_text8k")])
@pytest.mark.parametrize("name", ["attn_gate_pct", "attn_gate_roofline",
                                  "router_bias_moved_pct"])
def test_nothing_to_read_in_a_program_without_the_mechanism(name, trace,
                                                            cell):
    """The other families' programs (and the parent's): no op under
    ``model.attn_gate``, no counter.  A reader says nothing and does not
    raise."""
    other = context(trace, cell, {"count": 8192.0})
    assert read(name, other) is None


def test_the_norm_reader_reads_any_program_that_names_its_norms():
    """``model.norm`` is every decoder's since PR 37: a trace older than the
    name holds nothing for the reader, a newer one of another family does."""
    assert read("norm_pct", context(GPT2, "gpt2l_silo_fused",
                                    {"count": 8192.0})) is None
    assert read("norm_pct", context(MELLUM, "mellum2_silo_code8k",
                                    {"count": 8192.0})) is None
    assert 0 < read("norm_pct", context(KEYE, "keyevl2_silo_text8k",
                                        {"count": 8192.0})) < 100


def test_a_trace_without_the_counter_gives_no_share_of_tokens_moved(ctx):
    bare = context(TURN, CELL, {"count": 8192.0})
    assert read("router_bias_moved_pct", bare) is None
    assert read("attn_gate_pct", bare) == read("attn_gate_pct", ctx)
