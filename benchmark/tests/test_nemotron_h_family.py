"""The ``nemotron_h`` family's counts against numbers worked by hand for
``configs/nemotron-3-super-120b-a12b.json``: its parameters, the layers of
each kind, the FLOPs of a token by op class, the scan's work, the dense
products by group; and the file against the catalog row it was cut from."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, dense_groups, flops
from benchmark.families import nemotron_h, nemotron_h_plain

CONFIG = cells.read_json("configs", "nemotron-3-super-120b-a12b.json")
H, L, V = 4096, 8192, 16384  # hidden size, positions, the vocabulary's slice


@pytest.fixture(scope="module")
def tree():
    import jax

    bundle = nemotron_h.build_bundle(CONFIG)
    return jax.eval_shape(bundle.init, jax.random.PRNGKey(0))["params"]


def count(t):
    import jax

    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(t))


def test_parameters_by_the_tree_are_the_hand_count(tree):
    # in_proj: z 1024, x 1024, B and C 128 each, dt 16; taps and bias over
    # x, B, C; dt_bias, A_log, D; the gated norm; out_proj; the pre-norm
    mamba = (H * 2320 + 4 * 1280 + 1280 + 3 * 16 + 1024 + 1024 * H + H)
    assert count(tree["Block_0"]) == mamba == 13_708_592
    assert tree["Block_0"]["Mamba2Mixer_0"]["in_proj"]["kernel"].shape == (
        H, 2 * 1024 + 2 * 128 + 16)
    attention = H * 6 * 128 + 512 * H + H
    assert count(tree["Block_7"]) == attention == 5_246_976
    # router, its bias, the latent pair, 8 held relu2 experts, the shared
    # expert whole, the pre-norm
    experts = (H * 512 + 512 + 2 * H * 1024 + 8 * 2 * 1024 * 2688
               + 2 * H * 5376 + H)
    assert count(tree["Block_1"]) == experts == 98_570_752
    assert set(tree["Block_1"]["ExpertLayer_0"]) == {
        "router", "selection_bias", "latent_down", "up", "down", "latent_up"}
    total = 5 * mamba + attention + 5 * experts + 2 * V * H + H
    assert count(tree) == total == 700_865_520
    assert "700,865,520" in CONFIG["parameters"]


def test_the_eleven_layers_are_one_whole_period():
    kinds = nemotron_h_plain.layer_kinds(CONFIG)
    assert "".join("M" if m == "mamba" else "*" if m else "E"
                   for m, _ in kinds) == "MEMEMEM*EME"
    assert nemotron_h.layer_counts(CONFIG) == {"mamba": 5, "attention": 1,
                                               "sparse": 5, "dense": 0}
    pattern = CONFIG["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)  # 5 : 5 : 1, as here
    assert pattern[7] == "*"


def test_the_scans_own_work_a_token():
    # 16 heads x 5 x N 128 x P 64: decay, update and read of the state
    assert nemotron_h.ssm_flops_per_token(CONFIG) == 16 * 5 * 128 * 64 \
        == 655_360
    # x and y of 1024, B and C of 128 in bf16, dt of 16 in float32
    assert nemotron_h.ssm_bytes_per_token(CONFIG) \
        == 2 * (2 * 1024 + 2 * 128) + 4 * 16 == 4672
    # the bytes bound it at the v5e's peaks
    assert 4672 / 819e9 > 655_360 / 197e12


def test_forward_flops_of_a_token_by_class():
    fwd = nemotron_h.fwd_flops_per_unit(CONFIG)
    assert set(fwd) == {"matmul", "expert", "attention", "ssm"}
    weights = (5 * (H * 2320 + 1024 * H) + (H * 768 + 512 * H)
               + 5 * (H * 512 + 2 * H * 1024 + 2 * H * 5376) + H * V)
    assert weights == 413_466_624 and fwd["matmul"] == 2 * weights
    # 8 of 512 experts held, top 22: 0.34375 of a held assignment a token
    assert nemotron_h.held_share(CONFIG) == 22 * 8 / 512 == 0.34375
    assert nemotron_h.expert_flops_per_assignment(CONFIG) \
        == 4 * 1024 * 2688 == 11_010_048
    assert fwd["expert"] == 5 * 0.34375 * 11_010_048
    assert fwd["attention"] == L * (L + 1) // 2 * 4 * 128 * 4 / L
    assert fwd["ssm"] == 5 * 655_360
    assert sum(flops.train_flops_per_unit(CONFIG).values()) \
        == 3 * sum(fwd.values())
    # the head is 16 % of the credited forward here
    assert round(100 * 2 * H * V / sum(fwd.values()), 1) == 15.7


def test_the_dense_groups_sum_to_the_matmul_class():
    groups = dense_groups.fwd_flops(CONFIG)
    assert groups == {
        "head": 2 * H * V, "ssm_proj": 5 * 2 * H * 2320,
        "ssm_out": 5 * 2 * 1024 * H, "attn_proj": 2 * (H * 768 + 512 * H),
        "moe_router": 5 * 2 * H * 512, "moe_latent": 5 * 2 * 2 * H * 1024,
        "moe_shared": 5 * 2 * 2 * H * 5376, "mlp_dense": 0}
    assert sum(groups.values()) \
        == nemotron_h.fwd_flops_per_unit(CONFIG)["matmul"] == 826_933_248
    ours = 3 * sum(dense_groups.pass_bytes(CONFIG, L).values())
    assert ours == pytest.approx(
        nemotron_h.train_bytes_per_unit(CONFIG, L)["matmul"], rel=1e-12)


def test_attention_is_counted_as_the_flash_readers_take_it():
    assert nemotron_h.attention_pairs_per_sample(CONFIG) == L * (L + 1) // 2
    assert nemotron_h.attention_heads(CONFIG) == (4, 128)


def test_samples_come_from_the_vocabularys_slice():
    x, y = nemotron_h.make_samples(CONFIG, 2,
                                   np.random.default_rng(2**31 + 5))
    assert x.shape == y.shape == (2, L) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < CONFIG["vocab_size"] == V
    assert (y[:, :-1] == x[:, 1:]).all()
    assert nemotron_h.units_per_sample(CONFIG) == L


def test_the_cut_is_stated_with_what_it_stands_for():
    assert CONFIG["published"] == {
        "n_layer": 88, "mamba_num_heads": 128, "n_groups": 8,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "num_experts": 512, "vocab_size": 131072}
    assert set(CONFIG["reduced_how"]) == set(CONFIG["published"])
    # every split is the same eighth inside a tensor-parallel group of 8
    assert CONFIG["mamba_num_heads"] * 8 == 128
    assert CONFIG["num_attention_heads"] * 8 == 32
    # no width is cut: the shared expert is whole
    assert CONFIG["moe_shared_expert_intermediate_size"] == 5376
    assert CONFIG["vocab_size"] * 8 == 131072
    # the gated norm's group keeps its published 1024 channels
    assert (CONFIG["mamba_num_heads"] * CONFIG["mamba_head_dim"]
            // CONFIG["n_groups"]) == 128 * 64 // 8 == 1024
    assert CONFIG["experts_held"] == list(range(8))
    assert CONFIG["n_routed_experts"] == 512
    assert "chip 0 of 64" in CONFIG["deployment"]
    assert "8 tensor-parallel groups of 8" in CONFIG["deployment"]
    assert "352 rows" in CONFIG["deployment"]
    for key in ("positions", "router", "selection_bias_init_std",
                "latent_moe", "mamba_layer", "initial_weights",
                "learning_rate", "n_positions", "expand",
                "rescale_prenorm_residual"):
        assert key in CONFIG["assumed"], key
    assert CONFIG["rope_layer_types"] == []
    assert str(CONFIG["selection_bias_init_std"]) in CONFIG["assumed"][
        "selection_bias_init_std"]
    assert CONFIG["optimizer"] == {"name": "sgd", "lr": 0.003}
    assert CONFIG["remat"] is False and CONFIG["compute_dtype"] == "bf16"
    said = " ".join(CONFIG["departures"])
    for word in ("multi-token prediction", "frozen", "balance loss",
                 "depth 11"):
        assert word in said, word


def test_a_program_without_the_mechanisms_is_refused_before_anything_runs(
        monkeypatch):
    """The parent's decoder reads none of the file's new keys and would build
    eleven attention + expert layers from it: the family says so by name, at
    once."""
    from fedml_tpu.models import decoder

    old = decoder.DecoderConfig.from_dict.__func__

    def parents(cls, c):
        return old(cls, {k: v for k, v in c.items() if k not in (
            "hybrid_override_pattern", "mamba_num_heads", "mlp_hidden_act",
            "moe_latent_size", "moe_shared_expert_intermediate_size",
            "n_routed_experts")})

    monkeypatch.setattr(decoder.DecoderConfig, "from_dict",
                        classmethod(parents))
    with pytest.raises(ValueError, match="Mamba-2 mixers.*one sublayer.*relu2"):
        nemotron_h.build_bundle(CONFIG)


# A JSON-lines catalog of published model configurations, one object per line
# with "config" and "source_url"; the check is skipped where it is not given.
CATALOG = os.environ.get("MODEL_CONFIG_CATALOG", "")


def test_the_file_keeps_every_published_number():
    if not CATALOG or not os.path.exists(CATALOG):
        pytest.skip("MODEL_CONFIG_CATALOG names no catalog file")
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line]
    reduced = set(CONFIG["published"])
    for key, value in row[0]["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == row[0]["source_url"]
    entry = [c for c in cells.manifest()["configs"]
             if c["name"] == "nemotron-3-super-120b-a12b"][0]
    assert set(entry["reduced"]) == reduced == {
        "n_layer", "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "num_experts", "vocab_size"}
    assert CONFIG["num_hidden_layers"] == 88 and CONFIG["n_layer"] == 11


def test_the_controls_read_what_the_check_can_tell_apart():
    """``tools/control_nemotron_h.py`` at the rehearsal's toy widths: the
    sound reference passes, the one without the ``D x`` skip does not, and
    its error lies in every leaf the skip's output reaches."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "tools",
                                      "control_nemotron_h.py"),
         "--workload", "nemo3super_silo_agent8k", "--seed", "4100000077",
         "--rehearsal", "--controls", "sound,skip_dropped"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    sound, faulty = [json.loads(line) for line in out.stdout.splitlines()]
    assert (sound["control"], faulty["control"]) == ("sound", "skip_dropped")
    assert sound["ok"] and sound["delta_rel_l2"] < 1e-3
    assert not faulty["ok"] and faulty["delta_rel_l2"] > sound["delta_limit"]
    by_leaf = faulty["delta_rel_l2_by_leaf_kind"]
    assert by_leaf["Mamba2Mixer_0/D"] > 0.5
    assert set(sound["delta_rel_l2_by_leaf_kind"]) == set(by_leaf)
