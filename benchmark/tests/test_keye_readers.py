"""The readers the ``keyevl2_silo_text8k`` cell brought, on 52 ms cropped from
the builder's own trace of the cell on a v5e (PR 39; ``tools/crop_trace.py <trace> <out> 71 52``:
a step's turn from forward to backward: the last layer's index projections,
its choice, ``flash_fwd`` over the chosen keys, the head, and into that
layer's ``flash_bwd``), with a stand-in session and the counter of one step;
and on the other families' traces, where each finds nothing."""

import os
import re
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

TESTDATA = os.path.join(cells.ROOT, "testdata")
TURN = os.path.join(TESTDATA, "keyevl2_silo_text8k", "v5e_turn.textproto")
MELLUM = os.path.join(TESTDATA, "mellum2_silo_code8k", "v5e_step.textproto")
KIMI = os.path.join(TESTDATA, "kimilin_silo_doc8k", "v5e_turn.textproto")
GPT2 = os.path.join(TESTDATA, "gpt2l_silo_fused_v5e_30ms.textproto")
NEW = ["sparse_attn_pct", "sparse_select_pct", "sparse_attn_roofline",
       "sparse_select_roofline", "sparse_tiles_live_pct"]
# one step of one client through four layers under a fresh indexer: every one
# of a sequence's 136 causal tiles holds a chosen pair
TILES_LIVE = 4 * 136.0


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
    return context(TURN, "keyevl2_silo_text8k", {
        "count": 8192.0, "attn_tiles_live": TILES_LIVE})


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


def seconds(ctx, scope, *needles, category=None):
    """Self seconds of the ops whose last ``model.*`` segment is ``scope`` (a
    copy that serves two parts carries both names, joined by ``;``: it is the
    last one's) and whose ``tf_op`` holds every needle."""
    def keep(op):
        name = str(op.stats.get("tf_op", ""))
        parts = re.findall(r"model\.[a-z_]+", name)
        return bool(parts) and parts[-1] == scope and all(
            n in name for n in needles) and (
            category is None or op.stats.get("hlo_category") == category)

    return ctx.summary.seconds_where(keep)


def test_the_manifest_reads_them_in_this_cell_only():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["keyevl2_silo_text8k"]
        assert by_name[name]["moves"] == "tokens_per_s"
        assert by_name[name]["unit"] == "%"
    assert by_name["sparse_tiles_live_pct"]["source"] == "program_counter"
    assert {by_name[n]["layer"] for n in NEW} == {"model step", "kernels"}
    assert [by_name[n]["better"] for n in NEW] == [
        "lower", "lower", "higher", "higher", "lower"]


def test_the_scopes_a_reader_names_are_the_programs():
    from fedml_tpu.models.decoder import ATTN_TILES_LIVE
    from fedml_tpu.obs import scopes

    assert cells.load_layer_metric("sparse_attn_pct").SCOPE \
        == cells.load_layer_metric("sparse_attn_roofline").SPARSE \
        == scopes.ATTN_SPARSE
    assert cells.load_layer_metric("sparse_select_pct").SCOPES == (
        scopes.ATTN_INDEXER, scopes.ATTN_SELECT)
    assert cells.load_layer_metric("sparse_select_roofline").SCOPE \
        == scopes.ATTN_SELECT
    assert cells.load_layer_metric("sparse_tiles_live_pct").COUNTER \
        == ATTN_TILES_LIVE


def test_attention_share_is_the_scopes_seconds_both_ways(ctx):
    layer = seconds(ctx, "model.attn_sparse")
    assert 0 < layer < ctx.summary.busy_s
    assert read("sparse_attn_pct", ctx) == pytest.approx(
        100 * layer / ctx.summary.busy_s)
    assert seconds(ctx, "model.attn_sparse", "flash_fwd") > 0
    assert seconds(ctx, "model.attn_sparse", "flash_bwd", "transpose(") > 0


def test_choice_share_is_indexer_and_selection_forward_only(ctx):
    indexer = seconds(ctx, "model.attn_indexer")
    choice = seconds(ctx, "model.attn_select")
    assert 0 < indexer < choice
    assert read("sparse_select_pct", ctx) == pytest.approx(
        100 * (indexer + choice) / ctx.summary.busy_s)
    # nothing differentiates the choice
    assert seconds(ctx, "model.attn_select", "transpose(") == 0
    assert seconds(ctx, "model.attn_indexer", "transpose(") == 0
    # the choice runs inside the attention module's vmap, the projections
    # outside it: the trace's attention class holds the one and not the other
    in_class = ctx.summary.seconds_where(
        lambda op: op.klass == "attention" and "model.attn_select" in str(
            op.stats.get("tf_op", "")))
    assert in_class == pytest.approx(choice)
    assert ctx.summary.seconds_where(
        lambda op: op.klass == "attention" and "model.attn_indexer" in str(
            op.stats.get("tf_op", ""))) == 0


def test_attention_roofline_credits_the_chosen_pairs_only(ctx):
    kernels = seconds(ctx, "model.attn_sparse", "pallas_call",
                      category="custom-call")
    by_name = ctx.summary.seconds_where(
        lambda op: "flash_" in op.name
        and op.stats.get("hlo_category") == "custom-call")
    assert kernels == by_name > 0
    # 14,681,088 chosen pairs a layer, 4 layers, 32 q heads of 128, 4 x the
    # head size forward and 10 x backward
    flops = 58_724_352 * 14 * 128 * 32
    got = read("sparse_attn_roofline", ctx)
    assert got == pytest.approx(100 * flops / 197e12 / kernels)
    # the same seconds over twice the sequences read twice the share
    twice = context(TURN, "keyevl2_silo_text8k", {"count": 16384.0},
                    samples=2)
    assert read("sparse_attn_roofline", twice) == pytest.approx(2 * got)


def test_choice_roofline_credits_the_scores_and_one_pass_of_bytes(ctx):
    choice = seconds(ctx, "model.attn_select")
    flops = 2048 * 4 * 33_558_528  # 2 x 16 x 64 a causal pair, four layers
    moved = 12_608 * 8192 * 4  # qI, kI, w in and a row of the mask out
    least = max(flops / 197e12, moved / 819e9)
    assert least == flops / 197e12  # the scores bound it
    got = read("sparse_select_roofline", ctx)
    assert got == pytest.approx(100 * least / choice)


@pytest.mark.parametrize("live, want", [(TILES_LIVE, 100.0),
                                        (TILES_LIVE / 2, 50.0),
                                        (4 * 16.0, 100 * 16 / 136)])
def test_tiles_live_is_the_counter_over_the_causal_tiles(live, want):
    got = read("sparse_tiles_live_pct", context(
        TURN, "keyevl2_silo_text8k", {"count": 8192.0,
                                      "attn_tiles_live": live}))
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
                                  "sparse_attn_roofline",
                                  "sparse_select_roofline"])
def test_the_shares_stay_under_100_here(ctx, name):
    part = 0.25  # the crop is a quarter of a step
    whole_step = context(TURN, "keyevl2_silo_text8k", {"count": 8192.0 * part},
                         samples=part)
    assert 0 < read(name, whole_step) < 100


@pytest.mark.parametrize("trace, cell", [(GPT2, "gpt2l_silo_fused"),
                                         (MELLUM, "mellum2_silo_code8k"),
                                         (KIMI, "kimilin_silo_doc8k")])
@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_in_a_program_without_the_layer(name, trace, cell):
    """The other families' programs (and the parent's): no ``model.attn_*``
    scope of the sparse layer, no counter.  A reader says nothing and does
    not raise."""
    other = context(trace, cell, {"count": 8192.0})
    assert read(name, other) is None


def test_a_trace_without_the_counter_gives_no_tile_share(ctx):
    bare = context(TURN, "keyevl2_silo_text8k", {"count": 8192.0})
    assert read("sparse_tiles_live_pct", bare) is None
    assert read("sparse_attn_pct", bare) == read("sparse_attn_pct", ctx)
