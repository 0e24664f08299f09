"""``attention_kernel_pct`` on the recorded crops (all lax attention: 0) and
on the same crops with an attention op standing in for a fused kernel."""

import copy
import glob
import os
import types

import pytest

from benchmark import cells, trace_reduce

CROPS = sorted(glob.glob(os.path.join(cells.ROOT, "testdata", "*.textproto")))


def read(summary):
    return cells.load_layer_metric("attention_kernel_pct").read(
        types.SimpleNamespace(summary=summary))


@pytest.fixture(scope="module", params=CROPS, ids=os.path.basename)
def summary(request):
    return trace_reduce.reduce_trace(request.param)


def attention_ops(summary):
    return [op for d in summary.devices for op in d.ops
            if op.klass == "attention"]


def test_reads_zero_where_attention_is_lax_ops(summary):
    # the crop of a round's end holds no attention op at all
    assert read(summary) == (0.0 if attention_ops(summary) else None)


def test_reads_the_share_of_the_custom_calls(summary):
    s = copy.deepcopy(summary)
    ops = attention_ops(s)
    if not ops:
        pytest.skip("no attention op in this crop")
    longest = max(ops, key=lambda op: op.self_ns)
    longest.stats = {  # ops of one name share their stats
        **longest.stats, "hlo_category": "custom-call",
        "tf_op": "jit(f)/jvp(fed.model)/Block_0/MultiHeadAttention_0/vmap()/"
                 "pallas_call"}
    want = 100.0 * longest.self_ns / sum(op.self_ns for op in ops)
    assert read(s) == pytest.approx(want)
    assert 0.0 < want <= 100.0


def test_says_nothing_without_an_attention_class(summary):
    s = copy.deepcopy(summary)
    for op in attention_ops(s):
        op.klass = "other"
    assert read(s) is None
