"""The decoder's Nemotron-H family (``models/decoder.py``: Mamba-2 mixers
over ``ops/ssd.py``, layers that hold a mixer alone or an MLP alone, a latent
mixture of squared-ReLU experts behind a sigmoid router with a selection
bias, a squared-ReLU shared expert) against the plain reference that lives
with the benchmark (``benchmark/families/nemotron_h_plain.py``): float32,
seeded random weights, toy sizes."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lowered_programs  # noqa: E402
from test_decoder_kinds import round_of  # noqa: E402
from benchmark.families import nemotron_h_plain as plain  # noqa: E402
from fedml_tpu.models.base import COUNTERS  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    DENSE, FULL, NONE, RELU2, ROWS_BUFFERED, SPARSE, SSM, SSM_LOG_DECAY_MEAN,
    DecoderConfig, ExpertLayer, Mamba2Mixer, Relu2MLP, decoder_lm,
)
from fedml_tpu.models.transformer import MultiHeadAttention  # noqa: E402
from fedml_tpu.obs import scopes  # noqa: E402
from fedml_tpu.ops.ssd import ssd_chunked, ssd_recurrent  # noqa: E402

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                      "lowered_parent_pr44.json")


def toy_config(**over):
    """Every kind of layer the pattern names (Mamba, expert, Mamba,
    attention, expert, dense) at toy widths, in the published file's key
    names and this repository's own for what no published key states."""
    return {
        "vocab_size": 64, "hidden_size": 32, "n_layer": 6,
        "hybrid_override_pattern": "MEM*E-", "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
        "mamba_hidden_act": "silu", "mamba_proj_bias": False,
        "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8, "layer_norm_epsilon": 1e-5,
        "intermediate_size": 40, "moe_intermediate_size": 24,
        "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 20,
        "n_routed_experts": 16, "num_experts": 16,
        "experts_held": list(range(16)), "num_experts_per_tok": 3,
        "norm_topk_prob": True, "routed_scaling_factor": 5.0,
        "score_func": "sigmoid", "selection_bias_init_std": 0.02,
        "mlp_hidden_act": "relu2", "n_positions": 32, "rope_theta": 10000,
        "rope_layer_types": [], "n_group": 1, "topk_group": 1, **over}


WHOLE = toy_config()
SHARE = toy_config(num_experts=4, experts_held=[1, 4, 6, 7])  # 4 of 16 held


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# -- the scan ---------------------------------------------------------------------

def ssd_inputs(L, lowest, key=0, H=4, P=8, G=2, N=16):
    """Inputs whose log decays ``dt A`` reach down to ``lowest`` a token."""
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    dt = jax.random.uniform(k[1], (L, H), minval=1e-3, maxval=1.0)
    A = -jnp.exp(jnp.linspace(math.log(0.01), math.log(-lowest), H))
    return (jax.random.normal(k[0], (L, H, P)), dt, A,
            jax.random.normal(k[3], (L, G, N)),
            jax.random.normal(k[4], (L, G, N)))


@pytest.mark.parametrize("L, chunk, lowest", [
    (64, 16, -10.0), (100, 32, -1.0), (256, 128, -0.01), (37, 16, -3.0),
    (128, 128, -10.0)], ids=["4chunks", "padded", "2chunks", "odd", "1chunk"])
def test_the_chunked_scan_is_the_recurrence_in_values_and_gradients(
        L, chunk, lowest):
    args = ssd_inputs(L, lowest)
    assert float((args[1] * args[2]).min()) < 0.9 * lowest  # reaches it
    ours, theirs = ssd_chunked(*args, chunk), ssd_recurrent(*args)
    assert ours.shape == theirs.shape == (L, 4, 8)
    assert rel(ours, theirs) < 1e-5
    weight = jnp.sin(jnp.arange(L * 4 * 8.0)).reshape(L, 4, 8)

    def loss(fn):
        return lambda *a: (fn(*a) * weight).sum()

    g_ours = jax.grad(loss(functools.partial(ssd_chunked, chunk=chunk)),
                      argnums=range(5))(*args)
    g_theirs = jax.grad(loss(ssd_recurrent), argnums=range(5))(*args)
    for name, a, b in zip("x dt A B C".split(), g_ours, g_theirs):
        assert bool(jnp.isfinite(a).all()), name
        assert rel(a, b) < 1e-4, name


def test_the_chunked_scan_keeps_the_inputs_dtype_and_adds_in_float32():
    """bf16 inputs: every product of the scan takes bf16 operands and gives
    float32 sums (lowered only: the CPU runs no such dot), the decays stay
    float32, and ``y`` comes back in bf16."""
    import re

    x, dt, A, B, C = ssd_inputs(64, -1.0)
    low = [t.astype(jnp.bfloat16) for t in (x, B, C)]
    scan = jax.jit(functools.partial(ssd_chunked, chunk=16))
    out = jax.eval_shape(scan, low[0], dt, A, *low[1:])
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    text = scan.lower(low[0], dt, A, *low[1:]).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*", text)
    assert len(dots) == 4
    for dot in dots:
        assert re.search(r"\(tensor<[^>]*bf16>, tensor<[^>]*bf16>\) -> "
                         r"tensor<[^>]*f32>", dot), dot
    assert "exponential" in text and re.search(
        r"stablehlo\.exponential[^\n]*tensor<[^>]*f32>", text)


# -- the configuration ----------------------------------------------------------

def test_from_dict_reads_the_nemotron_h_keys_and_the_pattern():
    cfg = DecoderConfig.from_dict(WHOLE)
    assert cfg.layer_types == (SSM, NONE, SSM, FULL, NONE, NONE)
    assert cfg.mlp_types == (NONE, SPARSE, NONE, NONE, SPARSE, DENSE)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state) == (4, 8, 2, 16)
    assert (cfg.conv_kernel, cfg.chunk, cfg.ssm_conv_bias) == (4, 8, True)
    assert cfg.mlp_act == RELU2 and cfg.moe_latent_size == 16
    assert cfg.shared_expert_size == 20  # not num_shared_experts x width
    assert cfg.num_experts_routed == 16  # n_routed_experts
    assert cfg.rms_norm_eps == 1e-5  # layer_norm_epsilon
    assert cfg.router_activation == "sigmoid" and cfg.routed_scaling_factor == 5
    assert cfg.rope == ()  # no positions on the attention layer
    # the published pattern, read from the front to the depth
    deep = DecoderConfig.from_dict(toy_config(
        n_layer=11, hybrid_override_pattern="MEMEMEM*EMEMEMEM*EMEM"))
    assert "".join({SSM: "M", FULL: "*"}.get(k, "E")
                   for k in deep.layer_types) == "MEMEMEM*EME"


@pytest.mark.parametrize("over, match", [
    ({"hybrid_override_pattern": "MEX"}, "hybrid_override_pattern"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"mamba_hidden_act": "relu"}, "mamba_hidden_act"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias")])
def test_what_is_not_built_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig.from_dict(toy_config(**over))


def test_rescale_prenorm_residual_scales_every_branchs_last_projection():
    """Over ``sqrt(num_hidden_layers)``: Mamba's out_proj, attention's output,
    the shared expert's and the dense MLP's down-projections, the routed
    sum's up-projection; nothing else, and nothing without the key."""
    wide = toy_config(hidden_size=256, moe_latent_size=128, n_layer=6,
                      num_hidden_layers=64, intermediate_size=256,
                      moe_shared_expert_intermediate_size=256)
    assert DecoderConfig.from_dict(wide).residual_scale == 1.0
    scaled = {**wide, "rescale_prenorm_residual": True}
    assert DecoderConfig.from_dict(scaled).residual_scale == 1 / 8
    key = jax.random.PRNGKey(5)
    plain_init = decoder_lm(wide).init(key)["params"]
    ours = decoder_lm(scaled).init(key)["params"]
    last = {("Mamba2Mixer_0", "out_proj", "kernel"),
            ("MultiHeadAttention_0", "Dense_1", "kernel"),
            ("shared_expert", "down", "kernel"), ("mlp", "down", "kernel"),
            ("ExpertLayer_0", "latent_up")}
    scaled_leaves = 0
    for path, a in jax.tree_util.tree_flatten_with_path(ours)[0]:
        names = tuple(k.key for k in path)
        b = plain_init
        for k in names:
            b = b[k]
        scaled_leaves += names[1:] in last
        np.testing.assert_allclose(a, b / 8 if names[1:] in last else b,
                                   rtol=1e-6, err_msg=str(names))
    assert scaled_leaves == 2 + 1 + 2 * 2 + 1  # Mamba, attention, E, dense


def test_every_new_field_is_off_unless_a_key_states_it():
    from test_afmoe_decoder import WHOLE as TRINITY
    from test_decoder import WHOLE as MELLUM

    for published in (MELLUM, TRINITY):
        cfg = DecoderConfig.from_dict(published)
        assert cfg.ssm_heads == 0 and cfg.moe_latent_size == 0
        assert cfg.residual_scale == 1.0
        assert cfg.mlp_act == "silu_gated" and NONE not in cfg.layer_types
        assert NONE not in cfg.mlp_types


# -- the decoder against the plain reference -----------------------------------------

@pytest.fixture(scope="module", params=[WHOLE, SHARE], ids=["whole", "share"])
def model(request):
    cfg = request.param
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    return cfg, bundle, variables, x


def test_a_layer_holds_one_sublayer_and_its_norm(model):
    cfg, _, variables, _ = model
    params = variables["params"]
    assert set(variables) == {"params"}
    assert set(params["Block_0"]) == {"RMSNorm_0", "Mamba2Mixer_0"}
    assert set(params["Block_1"]) == {"RMSNorm_0", "ExpertLayer_0",
                                      "shared_expert"}
    assert set(params["Block_3"]) == {"RMSNorm_0", "MultiHeadAttention_0"}
    assert set(params["Block_5"]) == {"RMSNorm_0", "mlp"}
    mamba = params["Block_0"]["Mamba2Mixer_0"]
    # z, x, B and C of two groups, dt a head; taps over x, B and C
    assert mamba["in_proj"]["kernel"].shape == (32, 2 * 32 + 2 * 2 * 16 + 4)
    assert mamba["conv"].shape == (4, 32 + 64)
    assert mamba["conv_bias"].shape == (96,)
    assert mamba["norm"].shape == (32,) and mamba["D"].shape == (4,)
    experts = params["Block_1"]["ExpertLayer_0"]
    assert set(experts) == {"router", "selection_bias", "latent_down", "up",
                            "down", "latent_up"}  # no gate: relu2
    assert experts["up"].shape == (cfg["num_experts"], 16, 24)
    assert experts["down"].shape == (cfg["num_experts"], 24, 16)
    assert experts["latent_down"].shape == (32, 16)
    assert experts["latent_up"].shape == (16, 32)
    assert experts["router"].shape == (32, 16)
    assert set(params["Block_1"]["shared_expert"]) == {"up", "down"}
    assert params["Block_1"]["shared_expert"]["up"]["kernel"].shape == (32, 20)
    assert set(params["Block_5"]["mlp"]) == {"up", "down"}


def test_the_mamba_leaves_start_as_mamba2_does():
    params = decoder_lm(toy_config(mamba_num_heads=512, mamba_head_dim=1,
                                   n_groups=1)).init(
        jax.random.PRNGKey(2))["params"]["Block_0"]["Mamba2Mixer_0"]
    A = np.exp(np.asarray(params["A_log"]))
    assert 1 <= A.min() and A.max() <= 16 and A.std() > 2
    dt = np.log1p(np.exp(np.asarray(params["dt_bias"])))  # softplus
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.median(dt) == pytest.approx(math.sqrt(1e-3 * 0.1), rel=0.3)
    np.testing.assert_array_equal(params["D"], 1.0)
    np.testing.assert_array_equal(params["norm"], 1.0)


def test_forward_matches_the_plain_reference(model):
    cfg, bundle, variables, x = model
    logits, _ = bundle.apply_train(variables, x)
    assert rel(logits, plain.forward(cfg, variables["params"], x)) < 1e-5
    assert rel(bundle.apply_eval(variables, x), logits) < 1e-6


def grads_of(forward, params, x):
    y = jnp.roll(x, -1, axis=-1)

    def loss(params):
        logp = jax.nn.log_softmax(forward(params))
        return -jnp.take_along_axis(logp, y[..., None], -1).mean()

    return jax.value_and_grad(loss)(params)


def test_loss_and_every_gradient_match_the_reference(model):
    cfg, bundle, variables, x = model
    params = variables["params"]
    ours, g_ours = grads_of(
        lambda p: bundle.apply_train({"params": p}, x)[0], params, x)
    theirs, g_theirs = grads_of(lambda p: plain.forward(cfg, p, x), params, x)
    assert abs(float(ours - theirs)) / float(theirs) < 1e-5
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    flat_theirs = jax.tree_util.tree_leaves(g_theirs)
    assert len(flat_ours) == len(flat_theirs)
    for (path, a), b in zip(flat_ours, flat_theirs):
        name = jax.tree_util.keystr(path)
        if "selection_bias" in name:  # outside the gradient
            assert float(jnp.abs(a).max()) == 0.0 == float(jnp.abs(b).max())
        else:
            assert rel(a, b) < 1e-4, name
            assert float(jnp.abs(a).max()) > 0, name


def test_the_skip_and_the_gate_are_part_of_the_function(model):
    """Without ``D`` or with ``z``'s columns of in_proj zero the output moves:
    both terms are there, in the program and in the reference alike."""
    cfg, bundle, variables, x = model
    params = variables["params"]
    base = bundle.apply_eval(variables, x)
    mamba = params["Block_0"]["Mamba2Mixer_0"]
    no_skip = {**params, "Block_0": {**params["Block_0"], "Mamba2Mixer_0": {
        **mamba, "D": jnp.zeros_like(mamba["D"])}}}
    moved = bundle.apply_eval({"params": no_skip}, x)
    assert rel(moved, base) > 1e-3
    assert rel(moved, plain.forward(cfg, no_skip, x)) < 1e-5


def test_the_decay_counter_is_the_mean_of_dt_a_and_never_enters_variables():
    one = toy_config(n_layer=1)  # one Mamba layer
    bundle = decoder_lm(one)
    variables = bundle.init(jax.random.PRNGKey(3))
    x = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 64)
    _, new_vars = bundle.apply_train(variables, x)
    assert set(new_vars[COUNTERS]) == {SSM_LOG_DECAY_MEAN}
    p = jax.tree_util.tree_map(np.asarray, variables["params"])
    mamba = p["Block_0"]["Mamba2Mixer_0"]
    e = p["wte"]["embedding"][np.asarray(x)]
    a = e / np.sqrt((e * e).mean(-1, keepdims=True) + 1e-5) \
        * p["Block_0"]["RMSNorm_0"]["scale"]
    raw = (a @ mamba["in_proj"]["kernel"])[..., -4:] + mamba["dt_bias"]
    dt_a = -np.exp(mamba["A_log"]) * np.log1p(np.exp(raw))
    assert float(new_vars[COUNTERS][SSM_LOG_DECAY_MEAN]) == pytest.approx(
        float(dt_a.mean()), rel=1e-5)
    assert set(variables) == {"params"}


# -- the expert layer's two paths ----------------------------------------------------

ROUTED, TOP_K, LATENT, WIDTH, H = 16, 3, 16, 24, 32


def expert_params(key, held):
    ks = jax.random.split(key, 6)
    n = len(held)
    return {"router": jax.random.normal(ks[0], (H, ROUTED)) / math.sqrt(H),
            "latent_down": jax.random.normal(ks[1], (H, LATENT)) / math.sqrt(H),
            "up": jax.random.normal(ks[2], (n, LATENT, WIDTH)) / 4,
            "down": jax.random.normal(ks[3], (n, WIDTH, LATENT)) / 5,
            "latent_up": jax.random.normal(ks[4], (LATENT, H)) / 4,
            "selection_bias": 0.02 * jax.random.normal(ks[5], (ROUTED,))}


ROUTER = {"n_routed_experts": ROUTED, "num_experts_per_tok": TOP_K,
          "norm_topk_prob": True, "routed_scaling_factor": 5.0}


def layer_of(held):
    return ExpertLayer(ROUTED, tuple(held), TOP_K, WIDTH, True, "sigmoid", 5.0,
                       0.02, RELU2, LATENT)


@pytest.mark.parametrize("pushed, buffered", [
    (False, 1024), (True, 2 * 256 * 4)], ids=["short_buffer", "every_expert"])
def test_both_paths_of_the_latent_relu2_layer_match_the_reference(
        pushed, buffered):
    """512 tokens, 4 of 16 experts held: a level router's rows fit the short
    buffer (1024 of the worst case's 1536); a bias that sends every token to
    the held experts overflows it and every held expert runs over every
    token.  Both agree with the reference in value and gradient."""
    held = [2, 5, 11, 12]
    params = expert_params(jax.random.PRNGKey(5), held)
    if pushed:
        params["selection_bias"] = jnp.zeros((ROUTED,)).at[
            jnp.asarray(held)].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 256, H))
    cfg = {**ROUTER, "experts_held": held}
    weight = jnp.cos(jnp.arange(2 * 256 * H, dtype=jnp.float32)).reshape(
        2, 256, H)

    def ours(p, x):
        y, counters = layer_of(held).apply({"params": p}, x)
        return (y * weight).sum(), counters

    def theirs(p, x):
        y, _ = plain.expert_layer(cfg, x.reshape(-1, H), p)
        return (y.reshape(x.shape) * weight).sum()

    (v_ours, counters), g_ours = jax.value_and_grad(
        ours, argnums=(0, 1), has_aux=True)(params, x)
    v_theirs, g_theirs = jax.value_and_grad(theirs, argnums=(0, 1))(params, x)
    assert float(counters[ROWS_BUFFERED]) == buffered
    assert float(v_ours) == pytest.approx(float(v_theirs), rel=1e-5)
    assert rel(g_ours[1], g_theirs[1]) < 1e-5
    for name in ("router", "latent_down", "up", "down", "latent_up"):
        assert rel(g_ours[0][name], g_theirs[0][name]) < 1e-5, name
        assert float(jnp.abs(g_ours[0][name]).max()) > 0, name


# -- the chip's share -------------------------------------------------------------------

def test_the_8_shares_of_each_layer_add_up_to_the_uncut_reference_layer():
    """As 8 chips of a tensor-parallel group hold them: a Mamba layer's 16
    heads 2 a chip (one of 8 B/C groups each), an attention layer's 8 q heads
    one a chip (k/v head ``chip // 4``), an expert layer's 32 experts 4 a
    chip.  What every chip computes alike counts once: the router and the
    latent projections inside each share, the shared expert (whole on every
    chip: its width is not split) beside them; each share's output is a term
    of the uncut layer's."""
    L, h = 32, 32
    a = jax.random.normal(jax.random.PRNGKey(7), (L, h))
    k = jax.random.split(jax.random.PRNGKey(8), 16)
    # Mamba: 16 heads of 4, 8 groups of 8, d = 64
    Hm, P, G, N, d = 16, 4, 8, 8, 64
    whole = {"in_proj": {"kernel": jax.random.normal(
                 k[0], (h, 2 * d + 2 * G * N + Hm)) / math.sqrt(h)},
             "conv": jax.random.uniform(k[1], (4, d + 2 * G * N), minval=-.5,
                                        maxval=.5),
             "conv_bias": jax.random.uniform(k[2], (d + 2 * G * N,),
                                             minval=-.5, maxval=.5),
             "dt_bias": jax.random.normal(k[3], (Hm,)) - 3,
             "A_log": jnp.log(jax.random.uniform(k[4], (Hm,), minval=1,
                                                 maxval=16)),
             "D": jax.random.normal(k[5], (Hm,)),
             "norm": 1 + 0.1 * jax.random.normal(k[6], (d,)),
             "out_proj": {"kernel": jax.random.normal(k[7], (d, h)) / 8}}
    mcfg = {"mamba_num_heads": Hm, "mamba_head_dim": P, "n_groups": G,
            "ssm_state_size": N, "use_conv_bias": True,
            "layer_norm_epsilon": 1e-5}
    want = plain.mamba(mcfg, a, whole)
    total = 0.0
    for s in range(8):
        heads = np.arange(2 * s, 2 * s + 2)
        chans = np.concatenate([np.arange(h_ * P, (h_ + 1) * P)
                                for h_ in heads])
        bc = np.arange(s * N, (s + 1) * N)
        cols = np.concatenate([chans, d + chans, 2 * d + bc,
                               2 * d + G * N + bc, 2 * d + 2 * G * N + heads])
        xbc = np.concatenate([chans, d + bc, d + G * N + bc])
        share = {"in_proj": {"kernel": whole["in_proj"]["kernel"][:, cols]},
                 "conv": whole["conv"][:, xbc],
                 "conv_bias": whole["conv_bias"][xbc],
                 "dt_bias": whole["dt_bias"][heads],
                 "A_log": whole["A_log"][heads], "D": whole["D"][heads],
                 "norm": whole["norm"][chans],
                 "out_proj": {"kernel": whole["out_proj"]["kernel"][chans]}}
        y, _ = Mamba2Mixer(2, P, 1, N, 4, 8, True, 1e-5).apply(
            {"params": share}, a[None])
        total = total + y[0]
    assert rel(total, want) < 1e-5
    # attention: 8 q heads on 2 k/v heads of 8, one q head a chip
    Hq, Hkv, hd = 8, 2, 8
    qkv = jax.random.normal(k[8], (h, (Hq + 2 * Hkv) * hd)) / math.sqrt(h)
    o = jax.random.normal(k[9], (Hq * hd, h)) / 8
    acfg = {"num_attention_heads": Hq, "num_key_value_heads": Hkv,
            "head_dim": hd}
    want = plain.attention(acfg, a, {"Dense_0": {"kernel": qkv},
                                     "Dense_1": {"kernel": o}})
    total = 0.0
    for s in range(8):
        g = s // 4

        def cols(head):
            return np.arange(head * hd, (head + 1) * hd)

        share = {"Dense_0": {"kernel": qkv[:, np.concatenate([
            cols(s), cols(Hq + g), cols(Hq + Hkv + g)])]},
            "Dense_1": {"kernel": o[cols(s)]}}
        total = total + MultiHeadAttention(
            1, num_kv_heads=1, head_dim=hd).apply({"params": share},
                                                  a[None])[0]
    assert rel(total, want) < 1e-5
    # the expert layer: 32 routed experts 4 a chip, the shared expert once
    routed, width = 32, 40
    ex = expert_params(k[10], range(routed))
    ex["router"] = jax.random.normal(k[11], (h, routed)) / math.sqrt(h)
    ex["selection_bias"] = 0.02 * jax.random.normal(k[12], (routed,))
    shared = {"up": {"kernel": jax.random.normal(k[13], (h, width)) / 6},
              "down": {"kernel": jax.random.normal(k[14], (width, h)) / 6}}
    ecfg = {**ROUTER, "n_routed_experts": routed,
            "experts_held": list(range(routed))}
    want, _ = plain.expert_layer(ecfg, a, ex, shared)
    total = Relu2MLP(width).apply({"params": shared}, a[None])[0]
    for s in range(8):
        held = list(range(4 * s, 4 * s + 4))
        share = {**ex, "up": ex["up"][4 * s:4 * s + 4],
                 "down": ex["down"][4 * s:4 * s + 4]}
        y, _ = ExpertLayer(routed, tuple(held), TOP_K, WIDTH, True, "sigmoid",
                           5.0, 0.02, RELU2, LATENT).apply(
            {"params": share}, a[None])
        total = total + y[0]
    assert rel(total, want) < 1e-5


# -- the round path ---------------------------------------------------------------

def test_a_round_trains_every_kind_and_counts_the_decay():
    bundle, state, block, (new_state, metrics) = round_of(SHARE)
    assert set(new_state.variables) == {"params"}
    log_decay = float(metrics[SSM_LOG_DECAY_MEAN][0])
    # 2 clients x 2 steps x 2 Mamba layers of means below 0
    assert log_decay < 0 and np.isfinite(log_decay)
    old, new = state.variables["params"], new_state.variables["params"]
    for path in (("Block_0", "Mamba2Mixer_0", "A_log"),
                 ("Block_1", "ExpertLayer_0", "latent_up"),
                 ("Block_3", "MultiHeadAttention_0", "Dense_0", "kernel"),
                 ("Block_5", "mlp", "up", "kernel")):
        a, b = old, new
        for key in path:
            a, b = a[key], b[key]
        assert rel(b, a) > 0, path
    np.testing.assert_array_equal(
        new["Block_1"]["ExpertLayer_0"]["selection_bias"],
        old["Block_1"]["ExpertLayer_0"]["selection_bias"])


def test_one_round_agrees_with_the_benchmark_reference_through_the_driver():
    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.load_cell("nemo3super_silo_agent8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 2**31 + 11, jax.devices()[:1])
    assert isinstance(session.bundle, plain.PlainBundle)
    rounds, metrics = session.call()
    assert rounds == 1 and bench_run.call_ok(metrics, session.cohort)
    assert SSM_LOG_DECAY_MEAN in metrics
    agreement = bench_run.check_reference(cell, session, 2**31 + 11)
    assert agreement["ok"], agreement
    assert agreement["delta_rel_l2"] < 0.01 and agreement["loss_rel"] < 1e-5


def test_the_new_scopes_name_the_programs_ops_forward_and_backward():
    import re

    new = (scopes.SSM_PROJ, scopes.SSM_SCAN, scopes.SSM_OUT,
           scopes.MOE_LATENT)
    assert set(new) <= set(scopes.MODEL_SCOPES)
    assert not [s for s in new if re.search(r"fed\.[a-z_]+", s)]
    bundle = decoder_lm(SHARE)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(jax.grad(lambda p: bundle.apply_train(
        {"params": p}, x)[0].sum())).lower(variables["params"]).as_text(
        debug_info=True)
    for scope in new:
        assert scope in text, scope
        assert re.search(r"transpose\(jvp\([^\"]*" + re.escape(scope),
                         text), scope


# -- the accepted configurations are what they were ---------------------------------

@pytest.mark.parametrize("family", lowered_programs.LATER)
def test_a_later_familys_tree_and_round_program_are_the_parents(family):
    """With the new fields off the afmoe toy's parameter tree and lowered
    round are those of the commit before this family came (the copy in
    ``testdata/lowered_parent_pr44.json``; the four older families are held
    to theirs by ``test_afmoe_decoder.py``)."""
    with open(PINNED) as f:
        pinned = json.load(f)[family]
    got = lowered_programs.program_of(family)
    assert got["tree"] == pinned["tree"]
    assert got["round_sha256"] == pinned["round_sha256"]
