"""The ``keye_sparse`` family's counts against numbers worked by hand for
``configs/keye-vl-2.0-30b-a3b.json``: its parameters, the pairs its choice
keeps, the FLOPs of a token by op class, the indexer's uncredited work."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, dense_groups, flops
from benchmark.families import keye_sparse

CONFIG = cells.read_json("configs", "keye-vl-2.0-30b-a3b.json")
H, D, L = 2048, 128, 8192  # hidden size, head size, positions


def test_parameters_by_the_tree_are_the_hand_count():
    import jax

    bundle = keye_sparse.build_bundle(CONFIG)
    tree = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))["params"]
    count = lambda t: sum(int(np.prod(l.shape))  # noqa: E731
                          for l in jax.tree_util.tree_leaves(t))
    # q/k/v of 32 + 2 x 4 heads, the output product, a norm weight for q and k
    attention = H * 40 * D + 32 * D * H + 2 * D
    # 16 index heads of 64, one index key with its LayerNorm, 16 weights
    indexer = H * (16 * 64 + 64 + 16) + 2 * 64
    mixer = tree["Block_0"]["MultiHeadAttention_0"]
    assert count(mixer["indexer"]) == indexer == 2_261_120
    assert count(mixer) == attention + indexer == 21_135_744
    experts = H * 128 + 16 * 3 * H * 768
    assert count(tree["Block_0"]["ExpertLayer_0"]) == experts == 75_759_616
    layer = attention + indexer + experts + 2 * H
    assert layer == 96_899_456
    assert count(tree) == 4 * layer + 2 * 18992 * H + H == 465_391_104


def test_the_choice_keeps_2048_keys_of_a_long_row():
    # every pair of the first 2048 queries, 2048 of up to 8192 for the rest
    kept = 2048 * 2049 // 2 + (L - 2048) * 2048
    assert keye_sparse.chosen_pairs(CONFIG) == kept == 14_681_088
    causal = L * (L + 1) // 2
    assert causal == 33_558_528 and round(100 * kept / causal, 2) == 43.75
    assert keye_sparse.causal_pairs_per_sample(CONFIG) == 4 * causal
    assert keye_sparse.attention_pairs_per_sample(CONFIG) == 4 * kept \
        == 58_724_352
    assert keye_sparse.attention_heads(CONFIG) == (32, 128)
    short = {**CONFIG, "n_positions": 1024}  # every causal key is kept
    assert keye_sparse.chosen_pairs(short) == 1024 * 1025 // 2


def test_the_indexer_costs_2048_flops_a_pair_and_is_credited_nowhere():
    assert keye_sparse.index_flops_per_pair(CONFIG) == 2 * 16 * 64 == 2048
    # qI, kI and w in float32 and a row of the int8 mask
    assert keye_sparse.index_bytes_per_token(CONFIG) \
        == 4 * (16 * 64 + 64 + 16) + L == 12_608
    fwd = keye_sparse.fwd_flops_per_unit(CONFIG)
    assert set(fwd) == {"matmul", "expert", "attention"}
    index_products = 2 * 4 * H * (16 * 64 + 64 + 16)
    assert fwd["matmul"] + index_products == 2 * (
        4 * (H * 40 * D + 32 * D * H + H * 128 + H * 1104) + H * 18992)


def test_forward_flops_of_a_token_by_class():
    fwd = keye_sparse.fwd_flops_per_unit(CONFIG)
    projections = 4 * 2 * (H * 40 * D + 32 * D * H)
    routers, head = 4 * 2 * H * 128, 2 * H * 18992
    assert (projections, routers, head) == (150_994_944, 2_097_152,
                                            77_791_232)
    assert fwd["matmul"] == projections + routers + head == 230_883_328
    # 16 of 128 experts held, top 8: one held assignment a token a layer
    assert keye_sparse.held_share(CONFIG) == 1.0
    assert keye_sparse.expert_flops_per_assignment(CONFIG) == 9_437_184
    assert fwd["expert"] == 4 * 9_437_184
    assert fwd["attention"] == 58_724_352 * 4 * D * 32 / L == 117_448_704
    assert sum(flops.train_flops_per_unit(CONFIG).values()) \
        == 3 * sum(fwd.values()) == 3 * 386_080_768


def test_the_dense_groups_sum_to_the_matmul_class():
    groups = dense_groups.fwd_flops(CONFIG)
    assert groups == {"head": 77_791_232, "attn_proj": 150_994_944,
                      "moe_router": 2_097_152}
    assert sum(groups.values()) \
        == keye_sparse.fwd_flops_per_unit(CONFIG)["matmul"]


def test_dense_bytes_fall_with_the_batch():
    few = keye_sparse.train_bytes_per_unit(CONFIG, 1024)["matmul"]
    many = keye_sparse.train_bytes_per_unit(CONFIG, 8192)["matmul"]
    assert few > many > 0


def test_samples_come_from_the_vocabularys_slice():
    x, y = keye_sparse.make_samples(CONFIG, 2,
                                    np.random.default_rng(2**31 + 5))
    assert x.shape == y.shape == (2, L) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < CONFIG["vocab_size"] == 18992
    assert (y[:, :-1] == x[:, 1:]).all()
    assert keye_sparse.units_per_sample(CONFIG) == L


def test_the_cut_is_stated_with_what_it_stands_for():
    assert CONFIG["published"] == {"n_layer": 48, "num_experts": 128,
                                   "vocab_size": 151936}
    assert CONFIG["experts_held"] == list(range(16))
    assert CONFIG["num_experts_routed"] == 128
    assert CONFIG["vocab_size"] * 8 == 151936
    assert "chip 0 of 8" in CONFIG["deployment"]
    assert "512 rows" in CONFIG["deployment"]
    assert set(CONFIG["reduced_how"]) == set(CONFIG["published"])
    for key in ("qk_norm", "indexer_input", "indexer_key_norm",
                "chunk_sizes", "index_score_scale", "initial_weights"):
        assert key in CONFIG["assumed"], key
    said = " ".join(CONFIG["departures"])
    for word in ("vision tower", "KL term", "balance loss", "8-bit"):
        assert word in said, word


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Keye-VL-2.0-30B-A3B"' in line]
    reduced = set(CONFIG["published"])
    for key, value in row[0]["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key  # sa_config whole among them
    assert CONFIG["source"] == row[0]["source_url"]
    # no width is reduced: depth, experts held and the vocabulary's slice
    assert reduced == {"n_layer", "num_experts", "vocab_size"}
    assert CONFIG["num_hidden_layers"] == 48 and CONFIG["n_layer"] == 4
