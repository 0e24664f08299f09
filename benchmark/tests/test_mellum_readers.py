"""The readers the ``mellum2_silo_code8k`` cell brought, on one training step
cropped from the builder's own trace of the cell on a v5e (PR 32;
``tools/crop_trace.py <trace> <out> 300 100``: forward and backward of three
sliding layers and a full one), with a stand-in session and the counters of
one step; and on the ``gpt2-large`` trace, where each finds nothing."""

import os
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

TESTDATA = os.path.join(cells.ROOT, "testdata")
# in a directory of its own: ``test_flash_roofline.py`` takes every crop
# directly under ``testdata/`` for one of lax attention
STEP = os.path.join(TESTDATA, "mellum2_silo_code8k", "v5e_step.textproto")
GPT2 = os.path.join(TESTDATA, "gpt2l_silo_fused_v5e_30ms.textproto")
NEW = ["expert_pct", "expert_dispatch_pct", "expert_matmul_roofline",
       "flash_roofline", "expert_load_max_over_mean"]
# one step of one client: 8192 tokens through 4 expert layers
HELD, FULLEST = 32_801.0, 4_263.0


def context(trace, cell_name, metrics, samples):
    call = (0.0, 0.1, 1, {k: np.array([v]) for k, v in metrics.items()})
    return trace_reduce.Context(
        summary=trace_reduce.reduce_trace(trace),
        cell=cells.load_cell(cell_name),
        session=types.SimpleNamespace(
            padded_samples_per_round=lambda: samples),
        calls=[call], device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def ctx():
    return context(STEP, "mellum2_silo_code8k", {
        "count": 8192.0, "moe_assignments_held": HELD,
        "moe_expert_tokens_max": FULLEST}, samples=1)


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


def seconds(ctx, *needles, category=None):
    """Self seconds of the ops whose ``tf_op`` holds every needle."""
    return ctx.summary.seconds_where(lambda op: all(
        n in str(op.stats.get("tf_op", "")) for n in needles) and (
        category is None or op.stats.get("hlo_category") == category))


def test_the_manifest_reads_them_in_this_cell():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        # the flash kernels run in the ``gpt2-large`` cells too (PR 34)
        others = (["gpt2l_silo_fused", "gpt2l_silo_spmd4"]
                  if name == "flash_roofline" else [])
        assert by_name[name]["workloads"] == others + ["mellum2_silo_code8k"]
        assert by_name[name]["moves"] == "tokens_per_s"


def test_expert_shares_are_the_scopes_seconds(ctx):
    layer = seconds(ctx, "model.moe_")
    products = seconds(ctx, "model.moe_experts")
    assert 0 < products < layer < ctx.summary.busy_s
    assert read("expert_pct", ctx) == pytest.approx(
        100 * layer / ctx.summary.busy_s)
    assert read("expert_dispatch_pct", ctx) == pytest.approx(
        100 * (layer - products) / layer)
    # the layer is most of the step, and moving rows most of the layer
    assert 55 < read("expert_pct", ctx) < 80
    assert 55 < read("expert_dispatch_pct", ctx) < 80


def test_expert_roofline_counts_the_assignments_made(ctx):
    kernels = seconds(ctx, "model.moe_experts", category="custom-call")
    assert kernels > 0
    # forward + backward of three 2304 x 896 products an assignment
    flops = 3 * 3 * 2 * 2304 * 896 * HELD
    weights = 8 * 3 * 2304 * 896
    moved = 2 * 3 * (weights * 4 + 3 * (2304 + 896) * HELD)  # 4 layer-steps
    least = max(flops / 197e12, moved / 819e9)
    got = read("expert_matmul_roofline", ctx)
    assert got == pytest.approx(100 * least / kernels)
    assert 30 < got < 100
    # twice the assignments in the same seconds read twice the share
    twice = context(STEP, "mellum2_silo_code8k", {
        "count": 8192.0, "moe_assignments_held": 2 * HELD,
        "moe_expert_tokens_max": FULLEST}, samples=1)
    assert read("expert_matmul_roofline", twice) > 1.5 * got


def test_flash_roofline_credits_the_pairs_the_masks_need(ctx):
    kernels = ctx.summary.seconds_where(
        lambda op: "flash_" in op.name and op.stats.get("hlo_category")
        == "custom-call")
    assert kernels > 0
    pairs = 3 * 7_864_832 + 33_558_528  # one sequence, the four layers
    flops = pairs * (4 + 10) * 128 * 4
    got = read("flash_roofline", ctx)
    assert got == pytest.approx(100 * flops / 197e12 / kernels)
    assert 30 < got < 100


def test_load_is_the_fullest_expert_over_the_mean(ctx):
    assert read("expert_load_max_over_mean", ctx) == pytest.approx(
        8 * FULLEST / HELD)


def test_partition_identity_holds_in_the_new_cell(ctx):
    stages = ("forward_pct", "backward_pct", "optimizer_pct",
              "step_overhead_pct", "client_stack_pct", "aggregate_pct")
    total = sum(read(n, ctx) or 0.0 for n in stages)
    assert total + 100 - read("fed_scope_coverage_pct", ctx) \
        == pytest.approx(100, abs=1e-6)
    # the model.* scopes lie inside fed.model: none of them is a stage
    assert read("forward_pct", ctx) + read("backward_pct", ctx) > 80


@pytest.mark.parametrize("name", ["matmul_roofline", "step_mfu_pct",
                                  "attention_pct", "matmul_pct"])
def test_the_accepted_shares_stay_under_100_here(ctx, name):
    assert 0 < read(name, ctx) < 100


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_in_a_program_without_the_layer(name):
    """PR 22's program: no ``model.*`` scope, no counter, attention as lax
    ops.  A reader says nothing and does not raise."""
    gpt2 = context(GPT2, "gpt2l_silo_fused", {"count": 128 * 1024.0},
                   samples=128)
    assert read(name, gpt2) is None


def test_a_trace_without_counters_gives_no_counter_metric(ctx):
    bare = context(STEP, "mellum2_silo_code8k", {"count": 8192.0}, samples=1)
    assert read("expert_matmul_roofline", bare) is None
    assert read("expert_load_max_over_mean", bare) is None
    assert read("expert_pct", bare) == read("expert_pct", ctx)
