"""The readers the ``kimilin_silo_doc8k`` cell brought, on 50 ms cropped from
the builder's own trace of the cell on a v5e (PR 35; ``tools/crop_trace.py
<trace> <out> 30 50``: a step's turn from forward to backward: the latent
layer and the last linear-attention layer forward, the head, that layer's
backward and most of the latent layer's), with a stand-in session and the
counter of one step; and on the other families' traces, where each finds
nothing."""

import math
import os
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

TESTDATA = os.path.join(cells.ROOT, "testdata")
TURN = os.path.join(TESTDATA, "kimilin_silo_doc8k", "v5e_turn.textproto")
MELLUM = os.path.join(TESTDATA, "mellum2_silo_code8k", "v5e_step.textproto")
GPT2 = os.path.join(TESTDATA, "gpt2l_silo_fused_v5e_30ms.textproto")
NEW = ["linear_attn_pct", "linear_attn_roofline", "linear_attn_retention_pct",
       "latent_attn_roofline"]
# one step of one client through four KDA layers: the sum of their mean log
# decays as the traced run read it (-0.0296 a layer)
LOG_DECAY = -0.1184


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
    return context(TURN, "kimilin_silo_doc8k", {
        "count": 8192.0, "kda_log_decay_mean": LOG_DECAY})


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


def seconds(ctx, *needles, category=None):
    """Self seconds of the ops whose ``tf_op`` holds every needle."""
    return ctx.summary.seconds_where(lambda op: all(
        n in str(op.stats.get("tf_op", "")) for n in needles) and (
        category is None or op.stats.get("hlo_category") == category))


def test_the_manifest_reads_them_in_this_cell_only():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["kimilin_silo_doc8k"]
        assert by_name[name]["moves"] == "tokens_per_s"
        assert by_name[name]["unit"] == "%"
    assert by_name["linear_attn_retention_pct"]["source"] == "program_counter"
    assert {by_name[n]["layer"] for n in NEW} == {"model step", "kernels"}


def test_linear_attention_share_is_the_scopes_seconds(ctx):
    layer = seconds(ctx, "model.kda_")
    scan = seconds(ctx, "model.kda_scan")
    assert 0 < scan < layer < ctx.summary.busy_s
    assert read("linear_attn_pct", ctx) == pytest.approx(
        100 * layer / ctx.summary.busy_s)
    # the scan is most of the layer, forward and backward
    assert scan / layer > 0.6
    assert seconds(ctx, "model.kda_scan", "transpose(") > 0


def test_scan_roofline_counts_the_recurrences_own_work(ctx):
    scan = seconds(ctx, "model.kda_scan")
    # one step of 8192 tokens through four layers, three passes: 4 heads x
    # 3 x 2 x 128 x 128 FLOPs and 5 x 4 x 128 x 2 bytes a token and layer
    flops = 3 * 4 * 98_304 * 8192 * 4
    moved = 3 * 5_120 * 8192 * 4
    least = max(flops / 197e12, moved / 819e9)
    assert least == moved / 819e9  # the bytes bound it
    got = read("linear_attn_roofline", ctx)
    assert got == pytest.approx(100 * least / scan)
    assert 0 < got < 100
    # the same seconds over twice the tokens read twice the share
    twice = context(TURN, "kimilin_silo_doc8k", {"count": 16384.0}, samples=2)
    assert read("linear_attn_roofline", twice) == pytest.approx(2 * got)


def test_latent_roofline_reads_the_flash_kernels_under_the_scope(ctx):
    kernels = seconds(ctx, "model.attn_latent", "pallas_call",
                      category="custom-call")
    by_name = ctx.summary.seconds_where(
        lambda op: "flash_" in op.name
        and op.stats.get("hlo_category") == "custom-call")
    assert kernels == by_name > 0
    # 33,558,528 causal pairs, 4 q heads, 640 FLOPs forward + 1664 backward
    flops = 33_558_528 * (640 + 1664) * 4
    got = read("latent_attn_roofline", ctx)
    assert got == pytest.approx(100 * flops / 197e12 / kernels)
    assert got > 0


def test_retention_is_the_exp_of_the_mean_log_decay(ctx):
    assert read("linear_attn_retention_pct", ctx) == pytest.approx(
        100 * math.exp(LOG_DECAY / 4))
    assert 95 < read("linear_attn_retention_pct", ctx) < 100


def test_partition_identity_holds_in_the_new_cell(ctx):
    stages = ("forward_pct", "backward_pct", "optimizer_pct",
              "step_overhead_pct", "client_stack_pct", "aggregate_pct")
    total = sum(read(n, ctx) or 0.0 for n in stages)
    assert total + 100 - read("fed_scope_coverage_pct", ctx) \
        == pytest.approx(100, abs=1e-6)
    # the model.* scopes lie inside fed.model: none of them is a stage
    assert read("forward_pct", ctx) + read("backward_pct", ctx) > 80


@pytest.mark.parametrize("name", ["matmul_roofline", "step_mfu_pct",
                                  "attention_pct", "matmul_pct",
                                  "model_elementwise_pct"])
def test_the_accepted_shares_stay_under_100_here(ctx, name):
    whole_step = context(TURN, "kimilin_silo_doc8k", {"count": 8192.0 / 3},
                         samples=1 / 3)  # the crop is a third of a step
    assert 0 < read(name, whole_step) < 100


@pytest.mark.parametrize("trace, cell", [(GPT2, "gpt2l_silo_fused"),
                                         (MELLUM, "mellum2_silo_code8k")])
@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_in_a_program_without_the_layers(name, trace, cell):
    """The other families' programs: no ``model.kda_*`` or
    ``model.attn_latent`` scope, no counter.  A reader says nothing and does
    not raise."""
    other = context(trace, cell, {"count": 8192.0})
    assert read(name, other) is None


def test_a_trace_without_the_counter_gives_no_retention(ctx):
    bare = context(TURN, "kimilin_silo_doc8k", {"count": 8192.0})
    assert read("linear_attn_retention_pct", bare) is None
    assert read("linear_attn_pct", bare) == read("linear_attn_pct", ctx)
