"""The decoder's third and fourth mixer kinds (gated delta-rule linear
attention, latent attention with unequal head sizes), its dense MLP, shared
expert and sigmoid router (``models/decoder.py``) against the plain reference
that lives with the benchmark (``benchmark/families/kimi_linear_plain.py``):
float32, seeded random weights, toy sizes."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import kimi_linear_plain as plain  # noqa: E402
from fedml_tpu.models import decoder  # noqa: E402
from fedml_tpu.models.base import COUNTERS  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    ASSIGNMENTS_HELD, KDA_LOG_DECAY_MEAN, ROWS_BUFFERED, DecoderConfig,
    ExpertLayer, LatentQKV, LinearAttention, buffer_capacities, decoder_lm,
)
from fedml_tpu.models.transformer import MultiHeadAttention  # noqa: E402
from fedml_tpu.parallel.ring_attention import blockwise_attention  # noqa: E402

LINEAR_CONFIG = {
    "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
    "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4}


def toy_config(**over):
    """The leading dense layer and one period after it (KDA+dense, KDA+MoE,
    KDA+MoE, MLA+MoE, KDA+MoE) at toy widths, in the published key names."""
    return {
        "vocab_size": 64, "hidden_size": 32, "n_layer": 5, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "linear_attn_heads": 4, "linear_attn_config": LINEAR_CONFIG,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
        "v_head_dim": 8, "mla_use_nope": True, "rms_norm_eps": 1e-5,
        "first_k_dense_replace": 1, "intermediate_size": 48,
        "moe_intermediate_size": 24, "num_experts": 8,
        "num_experts_routed": 8, "experts_held": list(range(8)),
        "num_experts_per_token": 2, "num_shared_experts": 1,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "routed_scaling_factor": 2.446, "n_positions": 32, "chunk": 8, **over}


WHOLE = toy_config()
# 2 of 4 heads of either mixer, 3 of 8 experts: one chip's share
SHARE = toy_config(num_attention_heads=2, num_key_value_heads=2,
                   linear_attn_heads=2, num_experts=3, experts_held=[1, 4, 6])


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# -- the configuration ---------------------------------------------------------

def test_layer_and_mlp_kinds_come_from_the_published_keys():
    cfg = DecoderConfig.from_dict(WHOLE)
    assert cfg.layer_types == (decoder.LINEAR,) * 3 + (
        decoder.LATENT, decoder.LINEAR)
    assert cfg.mlp_types == (decoder.DENSE,) + (decoder.SPARSE,) * 4
    assert cfg.rope == () and cfg.router_activation == "sigmoid"
    assert (cfg.top_k, cfg.norm_topk_prob, cfg.routed_scaling_factor) == (
        2, True, 2.446)
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.conv_kernel,
            cfg.chunk) == (4, 8, 4, 8)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.shared_expert_size) == (16, 8, 4, 8, 24)
    # the share's head count overrides the published one inside the group
    assert DecoderConfig.from_dict(SHARE).linear_heads == 2
    # without a latent rank a full layer is plain softmax attention
    assert DecoderConfig.from_dict(
        {**WHOLE, "kv_lora_rank": None}).layer_types[3] == decoder.FULL
    with pytest.raises(ValueError, match="only 'sparse' and 'dense'"):
        DecoderConfig.from_dict({**WHOLE, "mlp_layer_types": ["conv"]})


# -- the decoder against the plain reference ----------------------------------

@pytest.fixture(scope="module", params=[WHOLE, SHARE], ids=["whole", "share"])
def model(request):
    cfg = request.param
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(1))
    x = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    return cfg, bundle, variables, x


def test_init_holds_parameters_only_and_no_positions(model):
    cfg, _, variables, _ = model
    assert set(variables) == {"params"}
    params = variables["params"]
    H = cfg["linear_attn_heads"]
    kda = params["Block_0"]["LinearAttention_0"]
    assert kda["q_proj"]["kernel"].shape == (32, H * 8)
    assert kda["q_conv"].shape == (4, H * 8) and kda["A_log"].shape == (H,)
    assert kda["f_a"]["kernel"].shape == (32, 8)  # low rank: the head size
    assert kda["f_b"]["kernel"].shape == (8, H * 8)
    assert kda["o_norm"]["scale"].shape == (8,)
    assert float(jnp.abs(kda["q_conv"]).max()) <= 0.5
    a = np.exp(np.asarray(kda["A_log"]))
    assert ((1 <= a) & (a <= 16)).all()
    dt = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert ((1e-3 * 0.999 <= dt) & (dt <= 1e-1 * 1.001)).all()
    assert set(params["Block_0"]) == {"LinearAttention_0", "RMSNorm_0",
                                      "RMSNorm_1", "mlp"}
    assert params["Block_0"]["mlp"]["gate"]["kernel"].shape == (32, 48)
    mla = params["Block_3"]["MultiHeadAttention_0"]
    heads = cfg["num_attention_heads"]
    assert mla["qkv"]["q"]["kernel"].shape == (32, heads * 12)
    assert mla["qkv"]["kv_a"]["kernel"].shape == (32, 16 + 4)
    assert mla["qkv"]["kv_b"]["kernel"].shape == (16, heads * 16)
    assert mla["Dense_0"]["kernel"].shape == (heads * 8, 32)
    assert params["Block_3"]["ExpertLayer_0"]["router"].shape == (32, 8)
    assert params["Block_3"]["shared_expert"]["down"]["kernel"].shape == (24, 32)
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    assert not [p for p in paths if "wpe" in p or p.endswith("['bias']")]


def test_forward_matches_the_plain_reference(model):
    cfg, bundle, variables, x = model
    logits, _ = bundle.apply_train(variables, x)
    assert rel(logits, plain.forward(cfg, variables["params"], x)) < 1e-5
    assert rel(bundle.apply_eval(variables, x), logits) < 1e-6


def test_loss_and_gradients_match_the_plain_reference(model):
    cfg, bundle, variables, x = model
    y = jnp.roll(x, -1, axis=-1)

    def loss_of(forward):
        def loss(params):
            logp = jax.nn.log_softmax(forward(params))
            return -jnp.take_along_axis(logp, y[..., None], -1).mean()
        return jax.value_and_grad(loss)(variables["params"])

    ours, g_ours = loss_of(
        lambda p: bundle.apply_train({"params": p}, x)[0])
    theirs, g_theirs = loss_of(lambda p: plain.forward(cfg, p, x))
    assert abs(float(ours - theirs)) / float(theirs) < 1e-5
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    flat_theirs = jax.tree_util.tree_leaves(g_theirs)
    assert len(flat_ours) == len(flat_theirs)
    for (path, a), b in zip(flat_ours, flat_theirs):
        # a head's A_log gradient is one sum over its tokens and channels of
        # terms of both signs, float32 on either side: by seed and by the
        # order of the sums it agrees to 3e-6 - 1.2e-5, every other leaf to
        # 1e-6 - 8e-6 (the op alone against the recurrence: 5e-6 at the most)
        name = jax.tree_util.keystr(path)
        assert rel(a, b) < (3e-5 if name.endswith("['A_log']") else 1e-5), name


# -- the shares add up -----------------------------------------------------------

def columns(heads, size):
    return np.concatenate([np.arange(h * size, (h + 1) * size) for h in heads])


def test_head_shares_of_a_linear_attention_layer_add_up():
    layer = LinearAttention(4, 8, 4, 8, 1e-5)
    a = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    p = layer.init(jax.random.PRNGKey(2), a)["params"]
    whole = jnp.stack([plain.kda(WHOLE, a[i], p) for i in range(2)])
    total, decays = 0.0, []
    for heads in ([0, 1], [2, 3]):  # as two chips would hold them
        c = columns(heads, 8)
        share = {
            **{f"{n}_proj": {"kernel": p[f"{n}_proj"]["kernel"][:, c]}
               for n in "qkv"},
            **{f"{n}_conv": p[f"{n}_conv"][:, c] for n in "qkv"},
            "f_a": p["f_a"], "g_a": p["g_a"], "o_norm": p["o_norm"],
            "f_b": {"kernel": p["f_b"]["kernel"][:, c]},
            "g_b": {"kernel": p["g_b"]["kernel"][:, c]},
            "A_log": p["A_log"][np.asarray(heads)], "dt_bias": p["dt_bias"][c],
            "b_proj": {"kernel": p["b_proj"]["kernel"][:, np.asarray(heads)]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][c]}}
        y, log_decay = LinearAttention(2, 8, 4, 8, 1e-5).apply(
            {"params": share}, a)
        total, decays = total + y, decays + [log_decay]
    assert rel(total, whole) < 1e-5
    # equal shares: the layer's mean log decay is the mean of the shares'
    assert float(layer.apply({"params": p}, a)[1]) == pytest.approx(
        float(np.mean(decays)), rel=1e-5)


def test_head_shares_of_a_latent_attention_layer_add_up():
    def module(heads):
        return MultiHeadAttention(heads, qkv=LatentQKV(
            heads, 16, 8, 4, 8, 1e-5, parent=None))

    a = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    p = module(4).init(jax.random.PRNGKey(2), a)["params"]
    whole = jnp.stack([plain.mla(WHOLE, a[i], p) for i in range(2)])
    assert rel(module(4).apply({"params": p}, a), whole) < 1e-5
    total = 0.0
    for heads in ([0, 1], [2, 3]):
        proj = p["qkv"]
        share = {"qkv": {
            "q": {"kernel": proj["q"]["kernel"][:, columns(heads, 12)]},
            # the down-projection and its norm are whole on every chip
            "kv_a": proj["kv_a"], "kv_norm": proj["kv_norm"],
            "kv_b": {"kernel": proj["kv_b"]["kernel"][:, columns(heads, 16)]}},
            "Dense_0": {"kernel": p["Dense_0"]["kernel"][columns(heads, 8)]}}
        total = total + module(2).apply({"params": share}, a)
    assert rel(total, whole) < 1e-5


SIGMOID = {"num_experts": 16, "num_experts_per_token": 4,
           "moe_renormalize": True, "routed_scaling_factor": 2.446}


def expert_params(key, h=32, f=24, routed=16, skew=None):
    ks = jax.random.split(key, 4)
    router = jax.random.normal(ks[0], (h, routed)) / math.sqrt(h)
    if skew is not None:  # push every token towards these experts
        router = router.at[:, jnp.asarray(skew)].add(
            jax.random.normal(ks[0], (h, 1)) * 2)
    return {"router": router,
            "gate": jax.random.normal(ks[1], (routed, h, f)) / math.sqrt(h),
            "up": jax.random.normal(ks[2], (routed, h, f)) / math.sqrt(h),
            "down": jax.random.normal(ks[3], (routed, f, h)) / math.sqrt(f)}


def run_layer(params, x, held, routed=16, top_k=4):
    ids = jnp.asarray(held)
    layer = ExpertLayer(routed, tuple(held), top_k, 24, True, "sigmoid", 2.446)
    return layer.apply({"params": {
        "router": params["router"],
        **{k: params[k][ids] for k in ("gate", "up", "down")}}}, x)


def test_expert_shares_add_up_with_the_shared_expert_counted_once():
    params = expert_params(jax.random.PRNGKey(2))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    shared = {"gate": {"kernel": jax.random.normal(ks[0], (32, 24)) / 6},
              "up": {"kernel": jax.random.normal(ks[1], (32, 24)) / 6},
              "down": {"kernel": jax.random.normal(ks[2], (24, 32)) / 5}}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    whole, _ = plain.expert_layer(SIGMOID, x.reshape(-1, 32), params, shared)
    # 8 shares of 2 experts, as 8 chips would hold them; what every chip
    # computes alike (the shared expert) enters the sum once
    total = decoder.GatedMLP(24).apply({"params": shared}, x)
    assigned = 0.0
    for s in range(8):
        y, counters = run_layer(params, x, [2 * s, 2 * s + 1])
        total, assigned = total + y, assigned + counters[ASSIGNMENTS_HELD]
    assert rel(total.reshape(-1, 32), whole) < 1e-5
    assert float(assigned) == 2 * 24 * 4  # every assignment on one share


# -- the sigmoid router -----------------------------------------------------------

def test_sigmoid_router_weights_against_a_count_by_hand():
    """Scores 0.75, 0.5, 0.25, 0.1 and 0.2, 0.5, 0.8, 0.6: the two largest,
    renormalised to sum to 1, times the factor; the bias is absent."""
    logit = lambda p: math.log(p / (1 - p))  # noqa: E731
    b = jnp.asarray([[logit(0.75), logit(0.5), logit(0.25), logit(0.1)],
                     [logit(0.2), logit(0.5), logit(0.8), logit(0.6)]])
    cfg = {"num_experts": 4, "num_experts_per_token": 2,
           "moe_renormalize": True, "routed_scaling_factor": 2.446}
    weight, chosen = plain.router(cfg, b, jnp.eye(4))
    want = np.array([[0.75 / 1.25, 0.5 / 1.25, 0, 0],
                     [0, 0, 0.8 / 1.4, 0.6 / 1.4]]) * 2.446
    np.testing.assert_allclose(weight, want, rtol=1e-6)
    assert sorted(chosen[0].tolist()) == [0, 1]
    assert sorted(chosen[1].tolist()) == [2, 3]
    # the program's layer weighs the same experts the same
    params = expert_params(jax.random.PRNGKey(0), h=4, routed=4)
    params["router"] = jnp.eye(4)
    whole, _ = plain.expert_layer(cfg, b, params)
    ours, _ = ExpertLayer(4, (0, 1, 2, 3), 2, 24, True, "sigmoid", 2.446
                          ).apply({"params": params}, b[:, None, :])
    assert rel(ours.reshape(-1, 4), whole) < 1e-5
    # and without the factor and the renormalisation, the scores themselves
    bare, _ = plain.router({**cfg, "moe_renormalize": False,
                            "routed_scaling_factor": 1.0}, b, jnp.eye(4))
    np.testing.assert_allclose(bare, [[0.75, 0.5, 0, 0], [0, 0, 0.8, 0.6]],
                               rtol=1e-6)


def test_softmax_stays_the_default_router():
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 32))
    params = expert_params(jax.random.PRNGKey(2))
    default = ExpertLayer(16, tuple(range(16)), 4, 24).apply(
        {"params": params}, x)[0]
    named = ExpertLayer(16, tuple(range(16)), 4, 24, True, "softmax", 1.0
                        ).apply({"params": params}, x)[0]
    assert float(jnp.abs(default - named).max()) == 0.0
    with pytest.raises(ValueError, match="unknown router activation"):
        ExpertLayer(16, tuple(range(16)), 4, 24, True, "tanh").apply(
            {"params": params}, x)


@pytest.mark.parametrize("skewed", [False, True], ids=["short", "fallback"])
def test_no_token_is_lost_at_8_of_256(skewed):
    """The cell's ratio: 8 of 256 experts held, top 8.  1024 tokens: a level
    router sends 256 rows, the short buffer is 512 of a worst case 8192; a
    router pushed towards the held experts overflows it and the layer runs
    every held expert over every token.  Either way the sums are the
    reference's."""
    held = list(range(8))
    assert buffer_capacities(1024, 8, 8, 256) == (512, 8192)
    assert buffer_capacities(8192, 8, 8, 256) == (4096, 65536)
    params = expert_params(jax.random.PRNGKey(2), routed=256,
                           skew=held if skewed else None)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1024, 32))
    cfg = {"num_experts": 8, "num_experts_routed": 256, "experts_held": held,
           "num_experts_per_token": 8, "moe_renormalize": True,
           "routed_scaling_factor": 2.446}
    share = {"router": params["router"],
             **{k: params[k][:8] for k in ("gate", "up", "down")}}
    want, chosen = plain.expert_layer(cfg, x.reshape(-1, 32), share)
    y, counters = run_layer(params, x, held, routed=256, top_k=8)
    assert rel(y.reshape(-1, 32), want) < 1e-5
    routed = int(np.isin(np.asarray(chosen), held).sum())
    assert float(counters[ASSIGNMENTS_HELD]) == routed
    assert (routed > 512) == skewed
    assert float(counters[ROWS_BUFFERED]) == (1024 * 8 if skewed else 512)


# -- unequal head sizes through the lax attention ---------------------------------

@pytest.mark.parametrize("kv_heads", [4, 2], ids=["own_kv", "shared_kv"])
def test_blockwise_attention_takes_a_v_head_size_of_its_own(kv_heads):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (40, 4, 12))
    k = jax.random.normal(ks[1], (40, kv_heads, 12))
    v = jax.random.normal(ks[2], (40, kv_heads, 8))
    w = jax.random.normal(ks[3], (40, 4, 8))

    def explicit(q, k, v):
        k, v = (jnp.repeat(t, 4 // kv_heads, axis=1) for t in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(12)
        s = jnp.where(jnp.tril(jnp.ones((40, 40), bool))[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    def blockwise(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_size=16)

    assert blockwise(q, k, v).shape == (40, 4, 8)
    assert rel(blockwise(q, k, v), explicit(q, k, v)) < 1e-5
    g_ours, g_theirs = (jax.grad(lambda *a: (f(*a) * w).sum(),
                                 argnums=(0, 1, 2))(q, k, v)
                        for f in (blockwise, explicit))
    for name, a, b in zip("qkv", g_ours, g_theirs):
        assert rel(a, b) < 1e-5, name


# -- scopes and the counter -------------------------------------------------------

def test_the_new_scopes_are_the_models_not_a_stage():
    """A stage reader takes an op's last ``fed.<name>`` segment as its stage:
    the model's own scopes must never match, or a stage would lose the op."""
    import re

    from fedml_tpu.obs import scopes

    new = {scopes.KDA_PROJ, scopes.KDA_GATES, scopes.KDA_SCAN, scopes.KDA_OUT,
           scopes.ATTN_LATENT, scopes.MLA_PROJ, scopes.MOE_SHARED,
           scopes.MLP_DENSE}
    assert new <= set(scopes.MODEL_SCOPES) and not new & set(scopes.SCOPES)
    assert len(set(scopes.MODEL_SCOPES)) == len(scopes.MODEL_SCOPES) == 23
    assert not [s for s in scopes.MODEL_SCOPES
                if re.search(r"fed\.[a-z_]+", s)]
    # and they are in the lowered program's op names, forward and backward
    bundle = decoder_lm(SHARE)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(jax.grad(lambda p: bundle.apply_train(
        {"params": p}, x)[0].sum())).lower(variables["params"]).as_text(
        debug_info=True)
    for name in new:
        assert name in text, name



def round_of(cfg):
    from fedml_tpu.algorithms.fedavg import ServerState, make_multi_round_fn
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    bundle = decoder_lm(cfg)
    fn = jax.jit(make_multi_round_fn(make_local_update(
        bundle, make_client_optimizer("sgd", 0.01), epochs=1), 1))
    key = jax.random.PRNGKey(4)
    state = ServerState(variables=bundle.init(key), opt_state=(),
                        round_idx=jnp.zeros((), jnp.int32), key=key)
    x = jax.random.randint(jax.random.PRNGKey(5), (2, 2, 2, 32), 0, 64)
    block = (x, jnp.roll(x, -1, -1), jnp.ones((2, 2, 2)), jnp.full((2,), 4.0),
             jnp.ones((2,)), jnp.arange(2, dtype=jnp.int32))
    return bundle, state, block, fn(state, *block)


def test_log_decay_counter_is_the_hand_mean_and_never_enters_variables():
    one = toy_config(n_layer=1)  # KDA + dense: one layer's mean, no expert
    bundle = decoder_lm(one)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    _, new_vars = bundle.apply_train(variables, x)
    assert set(new_vars[COUNTERS]) == {KDA_LOG_DECAY_MEAN}
    # by hand, in numpy, from the leaves
    p = jax.tree_util.tree_map(np.asarray, variables["params"])
    kda = p["Block_0"]["LinearAttention_0"]
    e = p["wte"]["embedding"][np.asarray(x)]
    a = e / np.sqrt((e * e).mean(-1, keepdims=True) + 1e-5) \
        * p["Block_0"]["RMSNorm_0"]["scale"]
    low = a @ kda["f_a"]["kernel"] @ kda["f_b"]["kernel"] + kda["dt_bias"]
    g = -np.exp(np.repeat(kda["A_log"], 8)) * np.log1p(np.exp(low))
    assert (g < 0).all()
    assert float(new_vars[COUNTERS][KDA_LOG_DECAY_MEAN]) == pytest.approx(
        g.mean(), rel=1e-5)
    # the reference's own decay agrees
    ref = np.stack([plain.log_decay(one, jnp.asarray(a[i]), kda)
                    for i in range(2)])
    assert ref.mean() == pytest.approx(g.mean(), rel=1e-5)
    # eval sows nothing
    assert bundle.apply_eval(variables, x).shape == (2, 32, 64)

    shared, state, block, (new_state, metrics) = round_of(SHARE)
    assert set(new_state.variables) == {"params"}
    assert COUNTERS not in metrics and ASSIGNMENTS_HELD in metrics
    # 2 clients x 2 steps, each the sum of the four KDA layers' means: the
    # four batches' sums at the round's first weights, as far as a step or
    # two of sgd move the decay
    steps = [float(shared.apply_train(state.variables, block[0][c, s])[1][
        COUNTERS][KDA_LOG_DECAY_MEAN]) for c in range(2) for s in range(2)]
    assert max(steps) < 0
    assert float(metrics[KDA_LOG_DECAY_MEAN][0]) == pytest.approx(
        sum(steps), rel=0.02)


def test_retention_reader_turns_the_counter_into_a_share():
    """``benchmark/layer_metrics/linear_attn_retention_pct.py`` on a made-up
    context: two traced calls of one round, each 4 clients x 4 steps over the
    cell's four KDA layers with a mean log decay of -0.05."""
    import types

    from benchmark import cells

    cell = cells.load_cell("kimilin_silo_doc8k")
    session = types.SimpleNamespace(padded_samples_per_round=lambda: 16)
    calls = [(0.0, 0.1, 1, {"count": np.array([16 * 8192.0]),
                            KDA_LOG_DECAY_MEAN: np.array([-0.05 * 64])})] * 2
    read = cells.load_layer_metric("linear_attn_retention_pct").read
    got = read(types.SimpleNamespace(calls=calls, cell=cell, session=session))
    assert got == pytest.approx(100 * math.exp(-0.05))
    bare = [(0.0, 0.1, 1, {"count": np.array([16 * 8192.0])})] * 2
    assert read(types.SimpleNamespace(calls=bare, cell=cell,
                                      session=session)) is None
    entry = {m["name"]: m for m in cells.manifest()["per_layer"]}[
        "linear_attn_retention_pct"]
    assert entry["workloads"] == ["kimilin_silo_doc8k"]
    assert (entry["source"], entry["moves"]) == ("program_counter",
                                                 "tokens_per_s")


# -- through the round path and the entry point -----------------------------------

def test_one_round_agrees_with_the_benchmark_reference_through_the_driver():
    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.load_cell("kimilin_silo_doc8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 11, jax.devices()[:1])
    assert isinstance(session.bundle, plain.PlainBundle)
    rounds, metrics = session.call()
    assert rounds == 1 and bench_run.call_ok(metrics, session.cohort)
    assert KDA_LOG_DECAY_MEAN in metrics and ASSIGNMENTS_HELD in metrics
    agreement = bench_run.check_reference(cell, session, 11)
    assert agreement["ok"], agreement
    assert agreement["delta_rel_l2"] < 0.01 and agreement["loss_rel"] < 1e-5


def test_it_is_reachable_by_name_from_the_experiment_entry_point(tmp_path):
    import json

    from fedml_tpu.experiments.run import ExperimentConfig, run_experiment

    path = tmp_path / "decoder.json"
    path.write_text(json.dumps(SHARE))
    out = run_experiment(ExperimentConfig(
        algorithm="fedllm", model="decoder_lm", model_config=str(path),
        dataset="fed_shakespeare", client_num_in_total=2,
        client_num_per_round=2, comm_round=1, batch_size=4, lr=0.01,
        max_samples_per_client=8, max_test_samples=8), log_fn=None)
    assert np.isfinite(out["final"]["test_loss"])
    assert out["history"][-1][KDA_LOG_DECAY_MEAN] < 0
