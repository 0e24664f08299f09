"""The ``kimi_linear`` family's counts against numbers worked by hand for
``configs/kimi-linear-48b-a3b.json``: its parameters, the FLOPs of a token by
op class, the recurrence's own count, the causal pairs."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, flops
from benchmark.families import kimi_linear, kimi_linear_plain

CONFIG = cells.read_json("configs", "kimi-linear-48b-a3b.json")
H, D = 2304, 128  # hidden size; the KDA head size


def test_layers_are_the_leading_dense_layer_and_one_period():
    assert kimi_linear_plain.layer_kinds(CONFIG) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    assert kimi_linear.layer_counts(CONFIG) == {
        "kda": 4, "mla": 1, "dense": 1, "sparse": 4}


def test_parameters_by_the_tree_are_the_hand_count():
    import jax

    bundle = kimi_linear.build_bundle(CONFIG)
    tree = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))["params"]
    count = lambda t: sum(int(np.prod(l.shape))  # noqa: E731
                          for l in jax.tree_util.tree_leaves(t))
    # a KDA mixer: q, k, v, o projections of 4 heads; two low-rank gates;
    # beta; three 4-tap filters; A_log, dt_bias and the output norm
    kda = (4 * H * 512 + 2 * (H * D + D * 512) + H * 4 + 3 * 4 * 512
           + 4 + 512 + D)
    assert count(tree["Block_0"]["LinearAttention_0"]) == kda == 5_455_492
    # the MLA mixer: q 4 x 192; down to 512 + 64; up to 4 x (128 + 128); o
    mla = H * 768 + H * 576 + 512 * 1024 + 512 * H + 512
    assert count(tree["Block_3"]["MultiHeadAttention_0"]) == mla == 4_801_024
    experts = H * 256 + 8 * 3 * H * 1024
    shared, dense = 3 * H * 1024, 3 * H * 9216
    assert count(tree["Block_1"]["ExpertLayer_0"]) == experts
    assert count(tree["Block_1"]["shared_expert"]) == shared
    assert count(tree["Block_0"]["mlp"]) == dense == 63_700_992
    norms = 2 * H
    layers = (kda + dense + norms) + 3 * (kda + experts + shared + norms) \
        + (mla + experts + shared + norms)
    assert count(tree) == layers + 2 * 20480 * H + H == 441_884_432


def test_the_recurrence_costs_three_products_with_the_state():
    # S~^T k, the rank-one update, S^T q: 3 x 2 x 128 x 128 a token and head
    assert kimi_linear.linear_attn_flops_per_token(CONFIG) == 4 * 98_304
    # q, k, v, g in and o out, 4 heads of 128, 2 bytes each
    assert kimi_linear.linear_attn_bytes_per_token(CONFIG) == 5_120


def test_pairs_are_the_causal_masks_own_count():
    assert kimi_linear.attention_pairs_per_sample(CONFIG) \
        == 8192 * 8193 // 2 == 33_558_528  # one MLA layer
    # a pair: scores over 192 and values over 128 forward; three products
    # over 192 and two over 128 backward
    assert kimi_linear.latent_pair_flops(CONFIG) == (640, 1664)
    assert kimi_linear.attention_heads(CONFIG) == (4, 160)


def test_forward_flops_of_a_token_by_class():
    fwd = kimi_linear.fwd_flops_per_unit(CONFIG)
    kda = 4 * H * 512 + 2 * (H * D + D * 512) + H * 4
    mla = H * 768 + H * 576 + 512 * 1024 + 512 * H
    sparse = H * 256 + 3 * H * 1024  # router and shared expert
    dense = 3 * H * 9216
    assert fwd["matmul"] == 2 * (4 * kda + mla + 4 * sparse + dense
                                 + H * 20480) == 336_306_176
    # 8 of 256 experts held, top 8: a quarter of a held assignment a token
    # an expert layer, three 2304 x 1024 products each
    assert kimi_linear.held_share(CONFIG) == 0.25
    assert kimi_linear.expert_flops_per_assignment(CONFIG) == 14_155_776
    assert fwd["expert"] == 4 * 0.25 * 14_155_776
    assert fwd["attention"] == 33_558_528 * 640 * 4 / 8192 == 10_487_040
    assert fwd["linear_attention"] == 4 * 4 * 98_304
    assert set(fwd) == {"matmul", "expert", "attention", "linear_attention"}
    assert sum(flops.train_flops_per_unit(CONFIG).values()) \
        == 3 * sum(fwd.values()) == 3 * 362_521_856


def test_the_experts_least_bytes():
    weights = 8 * 3 * H * 1024
    rows = 3 * (H + 1024)
    assert kimi_linear.expert_train_bytes(CONFIG, 2048, 1) \
        == 2 * 3 * (weights + rows * 2048)


def test_dense_bytes_fall_with_the_batch():
    few = kimi_linear.train_bytes_per_unit(CONFIG, 1024)["matmul"]
    many = kimi_linear.train_bytes_per_unit(CONFIG, 8192)["matmul"]
    assert few > many > 0


def test_samples_come_from_the_vocabularys_slice():
    x, y = kimi_linear.make_samples(CONFIG, 2, np.random.default_rng(2**31 + 5))
    assert x.shape == y.shape == (2, 8192) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < CONFIG["vocab_size"] == 20480
    assert (y[:, :-1] == x[:, 1:]).all()
    assert kimi_linear.units_per_sample(CONFIG) == 8192


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Kimi-Linear-48B-A3B-Instruct"' in line]
    reduced = set(CONFIG["published"])
    for key, value in row[0]["config"].items():
        if key in reduced:
            assert CONFIG[key] != value == CONFIG["published"][key]
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == row[0]["source_url"]
