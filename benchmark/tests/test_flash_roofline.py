"""``flash_roofline`` in the ``gpt2-large`` cells: on the recorded crops (all
lax attention: nothing to read, never 0) and on the same crops with an
attention op standing in for a fused kernel; the family's pair count."""

import copy
import glob
import os
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce
from benchmark.families import transformer_lm
from benchmark.layer_metrics import flash_roofline

CROPS = sorted(glob.glob(os.path.join(cells.ROOT, "testdata", "*.textproto")))
CELL = cells.load_cell("gpt2l_silo_fused")
SAMPLES = 128  # a round: 4 clients x 4 steps x batch 8


def read(summary):
    call = (0.0, 0.1, 1, {"count": np.array([SAMPLES * 1024.0])})
    return flash_roofline.read(trace_reduce.Context(
        summary=summary, cell=CELL, calls=[call], device_kind="TPU v5 lite",
        session=types.SimpleNamespace(
            padded_samples_per_round=lambda: SAMPLES)))


@pytest.fixture(scope="module", params=CROPS, ids=os.path.basename)
def summary(request):
    return trace_reduce.reduce_trace(request.param)


def attention_ops(summary):
    return [op for d in summary.devices for op in d.ops
            if op.klass == "attention"]


def test_reads_nothing_where_attention_is_lax_ops(summary):
    assert summary.seconds_where(flash_roofline.in_kernel) == 0
    assert read(summary) is None


def test_reads_the_needed_pairs_over_the_custom_calls_seconds(summary):
    s = copy.deepcopy(summary)
    ops = attention_ops(s)
    if not ops:
        pytest.skip("no attention op in this crop")
    longest = max(ops, key=lambda op: op.self_ns)
    longest.stats = {  # ops of one name share their stats
        **longest.stats, "hlo_category": "custom-call",
        "tf_op": "jit(f)/jvp(fed.model)/Block_0/MultiHeadAttention_0/vmap()/"
                 "pallas_call"}
    seconds = s.seconds_where(flash_roofline.in_kernel)
    assert 0 < seconds <= sum(op.self_ns for op in ops) / 1e9
    # 8 layers x 1024 x 1025 / 2 pairs a sample; 14 x 64 FLOPs a pair and
    # head forward and backward, 20 heads
    flops = 8 * 524_800 * SAMPLES * 14 * 64 * 20
    assert read(s) == pytest.approx(100.0 * flops / 197e12 / seconds)


def test_says_nothing_without_an_attention_class(summary):
    s = copy.deepcopy(summary)
    for op in attention_ops(s):
        op.klass = "other"
    assert read(s) is None


@pytest.mark.parametrize("L", [1, 7, 16, 1024])
def test_pairs_equal_a_count_of_the_causal_mask(L):
    toy = {"n_positions": L, "n_layer": 3}
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    assert transformer_lm.attention_pairs_per_sample(toy) == 3 * (j <= i).sum()


def test_the_cells_counts():
    assert transformer_lm.attention_pairs_per_sample(CELL.config) \
        == 8 * 524_800
    assert transformer_lm.attention_heads(CELL.config) == (20, 64)
    # a layer-step of batch 8 needs 75 GFLOP: 0.38 ms of the chip's peak
    # against the 1.67 ms the two kernels take (PERF.md, section 5)
    layer_step = 524_800 * 8 * 14 * 64 * 20
    assert layer_step == 75_235_328_000
    assert 100 * layer_step / 197e12 / 1.669e-3 == pytest.approx(22.9, abs=0.1)
