"""The ``afmoe`` family's counts against numbers worked by hand for
``configs/trinity-mini.json``: its parameters, the pairs its masks need, the
FLOPs of a token by op class, the dense products by group; and the file
against the catalog row it was cut from."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, dense_groups, flops
from benchmark.families import afmoe, afmoe_plain

CONFIG = cells.read_json("configs", "trinity-mini.json")
H, D, L, V = 2048, 128, 8192, 25024  # hidden size, head size, positions, slice


@pytest.fixture(scope="module")
def tree():
    import jax

    bundle = afmoe.build_bundle(CONFIG)
    return jax.eval_shape(bundle.init, jax.random.PRNGKey(0))["params"]


def count(t):
    import jax

    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(t))


def test_parameters_by_the_tree_are_the_hand_count(tree):
    # q/k/v of 32 + 2 x 4 heads, the gate's projection, the output product,
    # a norm weight for q and one for k
    attention = H * 40 * D + H * 32 * D + 32 * D * H + 2 * D
    assert count(tree["Block_0"]["MultiHeadAttention_0"]) == attention \
        == 27_263_232
    assert count(tree["Block_0"]["MultiHeadAttention_0"]["gate"]) \
        == H * 4096 == 8_388_608
    dense = attention + 4 * H + 3 * H * 6144
    assert count(tree["Block_0"]) == dense == 65_020_160
    # router, its selection bias, 8 held experts, the shared expert
    experts = H * 128 + 128 + 8 * 3 * H * 1024
    assert count(tree["Block_1"]["ExpertLayer_0"]) == experts == 50_593_920
    assert tree["Block_1"]["ExpertLayer_0"]["selection_bias"].shape == (128,)
    sparse = attention + 4 * H + experts + 3 * H * 1024
    assert count(tree["Block_1"]) == sparse == 84_156_800
    assert count(tree) == dense + 4 * sparse + 2 * V * H + H == 504_147_712
    assert "504,147,712" in CONFIG["parameters"]


def test_the_five_layers_are_a_dense_one_and_one_whole_period():
    kinds = afmoe_plain.layer_kinds(CONFIG)
    s, f = "sliding_attention", "full_attention"
    assert kinds == [(s, "dense"), (s, "sparse"), (s, "sparse"), (f, "sparse"),
                     (s, "sparse")]
    assert afmoe.layer_counts(CONFIG) == {"sliding": 4, "full": 1,
                                          "dense": 1, "sparse": 4}
    # the full layer stands where the published one does
    assert CONFIG["layer_types"][3] == f and CONFIG["layer_types"].count(f) == 8


def test_a_window_of_2048_keeps_44_percent_of_a_long_rows_pairs():
    pairs = afmoe.attention_pairs(CONFIG)
    window = 2048 * 2049 // 2 + (L - 2048) * 2048
    assert window == 14_681_088
    assert pairs == {"sliding_attention": window,
                     "full_attention": L * (L + 1) // 2}
    assert pairs["full_attention"] == 33_558_528
    assert round(100 * window / pairs["full_attention"], 2) == 43.75
    assert afmoe.attention_pairs_per_sample(CONFIG) \
        == 4 * window + pairs["full_attention"] == 92_282_880
    assert afmoe.attention_heads(CONFIG) == (32, 128)


def test_forward_flops_of_a_token_by_class():
    fwd = afmoe.fwd_flops_per_unit(CONFIG)
    assert set(fwd) == {"matmul", "expert", "attention"}
    weights = (5 * (H * 40 * D + H * 32 * D + 32 * D * H) + 3 * H * 6144
               + 4 * (H * 128 + 3 * H * 1024) + H * V)
    assert weights == 251_527_168 and fwd["matmul"] == 2 * weights
    # 8 of 128 experts held, top 8: half a held assignment a token a layer
    assert afmoe.held_share(CONFIG) == 0.5
    assert afmoe.expert_flops_per_assignment(CONFIG) == 12_582_912
    assert fwd["expert"] == 4 * 0.5 * 12_582_912 == 25_165_824
    assert fwd["attention"] == 92_282_880 * 4 * D * 32 / L == 184_565_760
    assert sum(flops.train_flops_per_unit(CONFIG).values()) \
        == 3 * sum(fwd.values()) == 3 * 712_785_920
    # the head is 14 % of the credited forward here
    assert round(100 * 2 * H * V / sum(fwd.values()), 1) == 14.4


def test_the_dense_groups_sum_to_the_matmul_class():
    groups = dense_groups.fwd_flops(CONFIG)
    assert groups == {
        "head": 2 * H * V, "attn_proj": 5 * 2 * (H * 40 * D + 32 * D * H),
        "attn_gate": 5 * 2 * H * 32 * D, "mlp_dense": 2 * 3 * H * 6144,
        "moe_router": 4 * 2 * H * 128, "moe_shared": 4 * 2 * 3 * H * 1024}
    assert groups["attn_gate"] == 83_886_080
    assert sum(groups.values()) \
        == afmoe.fwd_flops_per_unit(CONFIG)["matmul"] == 503_054_336
    # the gate's product is 4 of a layer's 13 attention products' weights
    assert 13 * groups["attn_gate"] == 4 * (groups["attn_gate"]
                                            + groups["attn_proj"])
    # the products' own bytes are the family's
    ours = 3 * sum(dense_groups.pass_bytes(CONFIG, L).values())
    assert ours == pytest.approx(
        afmoe.train_bytes_per_unit(CONFIG, L)["matmul"], rel=1e-12)


def test_dense_bytes_fall_with_the_batch():
    few = afmoe.train_bytes_per_unit(CONFIG, 1024)["matmul"]
    many = afmoe.train_bytes_per_unit(CONFIG, 8192)["matmul"]
    assert few > many > 0


def test_samples_come_from_the_vocabularys_slice():
    x, y = afmoe.make_samples(CONFIG, 2, np.random.default_rng(2**31 + 5))
    assert x.shape == y.shape == (2, L) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < CONFIG["vocab_size"] == V
    assert (y[:, :-1] == x[:, 1:]).all()
    assert afmoe.units_per_sample(CONFIG) == L


def test_the_cut_is_stated_with_what_it_stands_for():
    assert CONFIG["published"] == {"n_layer": 32, "num_dense_layers": 2,
                                   "num_experts": 128, "vocab_size": 200192}
    assert CONFIG["experts_held"] == list(range(8))
    assert CONFIG["num_experts_routed"] == 128
    assert CONFIG["vocab_size"] * 8 == 200192
    assert "chip 0 of 16" in CONFIG["deployment"]
    assert "512 rows" in CONFIG["deployment"]
    assert "a sixteenth" in CONFIG["deployment"]
    assert set(CONFIG["reduced_how"]) == set(CONFIG["published"])
    for key in ("positions_by_layer_kind", "attention_gate", "post_norm",
                "qk_norm", "mup_enabled", "selection_bias_init_std",
                "initial_weights", "learning_rate", "n_positions"):
        assert key in CONFIG["assumed"], key
    assert str(CONFIG["selection_bias_init_std"]) in CONFIG["assumed"][
        "selection_bias_init_std"]
    assert CONFIG["post_norm"] is True
    assert "0.1 and not 1" in CONFIG["assumed"]["post_norm"]
    assert "route_norm_eps" not in CONFIG  # below float32's resolution
    assert CONFIG["optimizer"] == {"name": "sgd", "lr": 0.003}
    said = " ".join(CONFIG["departures"])
    for word in ("balance rule", "frozen", "auxiliary loss", "random from",
                 "depth 5"):
        assert word in said, word


def test_a_program_without_the_mechanisms_is_refused_before_anything_runs(
        monkeypatch):
    """The parent's decoder reads none of the file's new keys and would build
    another model from it: the family says so by name, at once."""
    from fedml_tpu.models import decoder

    old = decoder.DecoderConfig.from_dict.__func__

    def parents(cls, c):
        return old(cls, {k: v for k, v in c.items() if k not in (
            "attention_gate", "post_norm", "rope_layer_types",
            "selection_bias_init_std", "mup_enabled")})

    monkeypatch.setattr(decoder.DecoderConfig, "from_dict",
                        classmethod(parents))
    with pytest.raises(ValueError, match="attn_gate.*post_norm.*selection_bias"):
        afmoe.build_bundle(CONFIG)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_keeps_every_published_number():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f if '"Trinity-Mini"' in line]
    reduced = set(CONFIG["published"])
    for key, value in row[0]["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key  # layer_types whole among them
    assert CONFIG["source"] == row[0]["source_url"]
    # no width is reduced: depth, leading dense layers, experts held, the
    # vocabulary's slice
    assert reduced == {"n_layer", "num_dense_layers", "num_experts",
                       "vocab_size"}
    assert CONFIG["num_hidden_layers"] == 32 and CONFIG["n_layer"] == 5


def test_the_controls_read_what_the_check_can_tell_apart():
    """``tools/control_afmoe.py`` at the rehearsal's toy widths: the sound
    reference passes, the one that ignores the selection bias does not, and
    its error lies in the router's and the experts' leaves."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "tools", "control_afmoe.py"),
         "--workload", "trinitymini_silo_chat8k", "--seed", "4100000077",
         "--rehearsal", "--controls", "sound,bias_ignored"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    sound, faulty = [json.loads(line) for line in out.stdout.splitlines()]
    assert (sound["control"], faulty["control"]) == ("sound", "bias_ignored")
    assert sound["ok"] and sound["delta_rel_l2"] < 1e-3
    assert not faulty["ok"] and faulty["delta_rel_l2"] > sound["delta_limit"]
    by_leaf = faulty["delta_rel_l2_by_leaf_kind"]
    assert "ExpertLayer_0/selection_bias" not in by_leaf  # no gradient
    assert by_leaf["ExpertLayer_0/router"] > 10 * by_leaf["lm_head/kernel"]
    assert set(sound["delta_rel_l2_by_leaf_kind"]) == set(by_leaf)
