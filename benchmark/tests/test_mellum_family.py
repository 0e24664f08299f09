"""The ``mellum_moe`` family's counts against numbers worked by hand for
``configs/mellum2-12b-a2.5b.json``: the (query, key) pairs its masks let
through, the FLOPs of a token by op class, the experts' bytes."""

import numpy as np
import pytest

from benchmark import cells, flops
from benchmark.families import mellum_moe

CONFIG = cells.read_json("configs", "mellum2-12b-a2.5b.json")


def test_pairs_are_the_masks_own_counts():
    pairs = mellum_moe.attention_pairs(CONFIG)
    # full: 1 + 2 + ... + 8192; sliding: the first 1024 rows grow to the
    # window, the other 7168 see 1024 keys each
    assert pairs["full_attention"] == 8192 * 8193 // 2 == 33_558_528
    assert pairs["sliding_attention"] == 524_800 + 7168 * 1024 == 7_864_832
    # depth 4 is one period: three sliding layers and a full one
    assert mellum_moe.attention_pairs_per_sample(CONFIG) == 57_153_024


@pytest.mark.parametrize("L, window", [(16, 5), (16, 16), (12, 40), (9, 1)])
def test_pairs_equal_a_count_of_the_mask(L, window):
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    toy = {"n_positions": L, "sliding_window": window}
    pairs = mellum_moe.attention_pairs(toy)
    assert pairs["full_attention"] == (j <= i).sum()
    assert pairs["sliding_attention"] == ((j <= i) & (i - j < window)).sum()


def test_forward_flops_of_a_token_by_class():
    fwd = mellum_moe.fwd_flops_per_unit(CONFIG)
    # a layer: qkv 2304 x 768, o 512 x 2304, router 2304 x 64; the head
    # 2304 x 12288; 2 FLOPs a multiply-add
    layer = 2304 * 768 + 512 * 2304 + 2304 * 64
    assert fwd["matmul"] == 2 * (4 * layer + 2304 * 12288) == 81_395_712
    # 8 of 64 experts held, top 8: one held assignment a token a layer in
    # expectation, three 2304 x 896 products each
    assert mellum_moe.held_share(CONFIG) == 1.0
    assert mellum_moe.expert_flops_per_assignment(CONFIG) == 12_386_304
    assert fwd["expert"] == 4 * 12_386_304
    # scores and values: 4 x 128 a pair and q head, 4 q heads, over the
    # pairs the masks need (not 4 layers x 8192 keys: 2.35 times as many)
    assert fwd["attention"] == 57_153_024 * 4 * 128 * 4 / 8192 == 14_288_256
    assert sum(flops.train_flops_per_unit(CONFIG).values()) \
        == 3 * (81_395_712 + 49_545_216 + 14_288_256)


def test_the_experts_least_bytes():
    # one layer's step with 8192 held assignments: the 8 experts' three
    # matrices three times (forward, backward, gradient) and six rows of
    # activations a product pass and assignment, 2 bytes each
    weights = 8 * 3 * 2304 * 896
    rows = 2 * (2304 + 896) + (896 + 2304)
    assert mellum_moe.expert_train_bytes(CONFIG, 8192, 1) \
        == 2 * 3 * (weights + rows * 8192)


def test_dense_bytes_fall_with_the_batch():
    few = mellum_moe.train_bytes_per_unit(CONFIG, 1024)["matmul"]
    many = mellum_moe.train_bytes_per_unit(CONFIG, 8192)["matmul"]
    assert few > many > 0


def test_samples_come_from_the_vocabularys_slice():
    x, y = mellum_moe.make_samples(CONFIG, 2, np.random.default_rng(2**31 + 5))
    assert x.shape == y.shape == (2, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < CONFIG["vocab_size"] == 12288
    assert (y[:, :-1] == x[:, 1:]).all()
    assert mellum_moe.units_per_sample(CONFIG) == 8192
