"""The plain reference of the ``kimi_linear`` family: the forward pass of a
Kimi-Linear-style decoder written out in ``jax.numpy`` and float32 from the
published equations, over the program's own parameter tree.

No kernel, no ``vmap``, no chunked form, no grouped product, no code of
``fedml_tpu``.  Pre-norm residual blocks, ``RMSNorm(x) = x / sqrt(mean(x^2) +
eps) * g``, no positional encoding anywhere.  A layer's mixer is

- **KDA** (gated delta-rule linear attention): ``q, k, v = silu(conv(a W))``
  with ``conv`` a causal depthwise convolution written as its shifted
  multiply-adds; ``q`` and ``k`` L2-normed a head, ``q`` also over ``sqrt(d)``;
  a log decay a channel ``g = -exp(A_log) * softplus(Wfb (Wfa a) + dt_bias)``;
  ``beta = sigmoid(a Wbeta)``; then **the recurrence, a token at a time**:
  ``S~ = Diag(exp(g_t)) S``, ``S = S~ + beta_t k_t (v_t - S~^T k_t)^T``,
  ``o_t = S^T q_t``; ``o`` RMS-normed a head, gated by ``sigmoid(Wgb (Wga a))``
  and projected.  The recurrence is a ``lax.scan`` over the tokens in segments
  of ``SEGMENT`` under ``jax.checkpoint``: a backward keeps one state a segment
  (2 GB of states a layer at 8192 tokens otherwise);
- **MLA** (latent attention): ``q`` heads of ``nope + rope`` from the input;
  keys' ``nope`` part and values expanded from the normed ``kv_lora_rank``
  latent, the keys' last ``rope`` columns one part all heads share, not
  rotated; explicit scores over ``sqrt(nope + rope)`` in query blocks, causal
  mask, softmax.

and its MLP a dense gated MLP (the leading ``first_k_dense_replace`` layers) or
the expert layer: sigmoid scores over all routed experts, **its own** top-k
(the selection bias is zero), the chosen renormalised and scaled by
``routed_scaling_factor``, a Python loop over the experts held, each applied to
every token and weighted by its routing weight or 0, plus the shared expert.
What the absent experts would add is left out, as in the program.

Every layer runs under ``jax.checkpoint``.  Matmul precision is the caller's
(``reference.reference_round`` sets ``highest``).  Assumptions and departures
from the published model: the configuration file's ``assumed`` and
``departures``.
"""

from __future__ import annotations

import functools
import math

Q_BLOCK = 2048  # queries a block of explicit scores (as ``mellum_moe_plain``)
SEGMENT = 64  # tokens of the recurrence between two kept states
L2_EPS = 1e-6


def layer_kinds(config: dict) -> list:
    """[(mixer, mlp)] a layer: "kda" or "mla", "dense" or "sparse"; layers
    are numbered from 1 in ``linear_attn_config``."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    return [("kda" if i + 1 in kda else "mla",
             "dense" if i < config["first_k_dense_replace"] else "sparse")
            for i in range(config["n_layer"])]


def linear_heads(config: dict) -> int:
    return config.get("linear_attn_heads",
                      config["linear_attn_config"]["num_heads"])


def conv(u, taps):
    """``y_t = sum_j taps[j] * u_{t - (K - 1) + j}``, zeros before the
    sequence starts: u [L, channels], taps [K, channels]."""
    import jax.numpy as jnp

    K, L = taps.shape[0], u.shape[0]
    y = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j  # taps[j] weighs the token ``back`` positions before
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:L - back]], axis=0)
        y = y + taps[j] * shifted
    return y


def delta_rule(q, k, v, g, beta):
    """The recurrence: q, k, g [L, H, d], v [L, H, d], beta [L, H] -> o."""
    import jax
    import jax.numpy as jnp

    L, H, d = q.shape
    seg = SEGMENT if L % SEGMENT == 0 else L

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x
        S = jnp.exp(g_t)[:, :, None] * S                        # S~
        stored = jnp.einsum("hd,hde->he", k_t, S)               # S~^T k
        S = S + beta_t[:, None, None] * k_t[:, :, None] * (
            v_t - stored)[:, None, :]
        return S, jnp.einsum("hd,hde->he", q_t, S)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(t.reshape(L // seg, seg, *t.shape[1:])
               for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((H, d, v.shape[-1]), q.dtype), xs)
    return o.reshape(L, H, v.shape[-1])


def router(config: dict, b, w_router):
    """b [T, h] -> (weights [T, routed], zero off the chosen; chosen ids
    [T, top_k])."""
    import jax
    import jax.numpy as jnp

    routed = config.get("num_experts_routed", config["num_experts"])
    score = jax.nn.sigmoid(b @ w_router)  # an expert; the bias is zero
    chosen = jnp.argsort(-score, axis=-1)[:, :config["num_experts_per_token"]]
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if config["moe_renormalize"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    picked = picked * config["routed_scaling_factor"]
    weight = jnp.zeros((b.shape[0], routed), jnp.float32)
    return weight.at[jnp.arange(b.shape[0])[:, None], chosen].set(
        picked), chosen


def gated_mlp(b, p):
    import jax

    return (jax.nn.silu(b @ p["gate"]["kernel"]) * (b @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def expert_layer(config: dict, b, p, shared=None):
    """b [T, h] -> (the held experts' part of the routed sum, plus the
    shared expert where ``shared`` holds one, [T, h]; chosen ids)."""
    import jax
    import jax.numpy as jnp

    held = list(config.get("experts_held", range(config["num_experts"])))
    weight, chosen = router(config, b, p["router"])
    y = jnp.zeros_like(b)
    for slot, e in enumerate(held):
        f = (jax.nn.silu(b @ p["gate"][slot]) * (b @ p["up"][slot])) \
            @ p["down"][slot]
        y = y + weight[:, e:e + 1] * f
    if shared is not None:
        y = y + gated_mlp(b, shared)
    return y, chosen


def rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def log_decay(config: dict, a, p):
    """``g`` [L, H, d] of one sequence's normed input a [L, h]."""
    import jax
    import jax.numpy as jnp

    H, d = linear_heads(config), config["linear_attn_config"]["head_dim"]
    low = (a @ p["f_a"]["kernel"]) @ p["f_b"]["kernel"]
    return -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        low + p["dt_bias"]).reshape(a.shape[0], H, d)


def kda(config: dict, a, p):
    """The KDA mixer of one sequence: a [L, h], the normed input."""
    import jax
    import jax.numpy as jnp

    H, d = linear_heads(config), config["linear_attn_config"]["head_dim"]
    L = a.shape[0]

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    def mixed(name):
        return jax.nn.silu(conv(a @ p[f"{name}_proj"]["kernel"],
                                p[f"{name}_conv"])).reshape(L, H, d)

    q, k, v = l2(mixed("q")) / math.sqrt(d), l2(mixed("k")), mixed("v")
    beta = jax.nn.sigmoid(a @ p["b_proj"]["kernel"])
    o = rms(delta_rule(q, k, v, log_decay(config, a, p), beta),
            p["o_norm"]["scale"], config["rms_norm_eps"])
    gate = jax.nn.sigmoid((a @ p["g_a"]["kernel"]) @ p["g_b"]["kernel"])
    return (o.reshape(L, H * d) * gate) @ p["o_proj"]["kernel"]


def mla(config: dict, a, p):
    """The MLA mixer of one sequence: a [L, h], the normed input."""
    import jax
    import jax.numpy as jnp

    H = config["num_attention_heads"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope, dv = config["qk_rope_head_dim"], config["v_head_dim"]
    L = a.shape[0]
    pos = jnp.arange(L)

    @jax.checkpoint
    def attend_block(q, k, v, qpos):
        """q [bq, H, nope + rope] at positions qpos against all of k, v."""
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(nope + rope)
        seen = pos[None, :] <= qpos[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    proj = p["qkv"]
    q = (a @ proj["q"]["kernel"]).reshape(L, H, nope + rope)
    c = a @ proj["kv_a"]["kernel"]
    latent = rms(c[:, :rank], proj["kv_norm"]["scale"], config["rms_norm_eps"])
    kv = (latent @ proj["kv_b"]["kernel"]).reshape(L, H, nope + dv)
    shared = jnp.broadcast_to(c[:, None, rank:], (L, H, rope))  # not rotated
    k = jnp.concatenate([kv[:, :, :nope], shared], axis=-1)
    out = [attend_block(q[i:i + Q_BLOCK], k, kv[:, :, nope:],
                        pos[i:i + Q_BLOCK]) for i in range(0, L, Q_BLOCK)]
    return jnp.concatenate(out, axis=0).reshape(L, H * dv) \
        @ p["Dense_0"]["kernel"]


def forward(config: dict, params, ids, with_selection: bool = False):
    """Logits [B, L, V] float32 of token ids [B, L]; ``with_selection`` also
    returns every expert layer's chosen expert ids [B * L, top_k]."""
    import jax
    import jax.numpy as jnp

    eps = config["rms_norm_eps"]

    def layer(x, p, mixer, mlp):
        a = rms(x, p["RMSNorm_0"]["scale"], eps)
        mix = functools.partial(kda, config, p=p["LinearAttention_0"]) \
            if mixer == "kda" else functools.partial(
                mla, config, p=p["MultiHeadAttention_0"])
        x = x + jnp.stack([mix(a[i]) for i in range(x.shape[0])])
        b = rms(x, p["RMSNorm_1"]["scale"], eps).reshape(-1, x.shape[-1])
        if mlp == "dense":
            return x + gated_mlp(b, p["mlp"]).reshape(x.shape), None
        y, chosen = expert_layer(
            config, b, p["ExpertLayer_0"],
            p["shared_expert"] if config["num_shared_experts"] else None)
        return x + y.reshape(x.shape), chosen

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    x = params["wte"]["embedding"][ids]
    selection = []
    for i, (mixer, mlp) in enumerate(layer_kinds(config)):
        x, chosen = jax.checkpoint(
            lambda x, p, mixer=mixer, mlp=mlp: layer(x, p, mixer, mlp))(
            x, params[f"Block_{i}"])
        if chosen is not None:
            selection.append(chosen)
    logits = rms(x, params["norm_f"]["scale"], eps) \
        @ params["lm_head"]["kernel"]
    return (logits, selection) if with_selection else logits


class PlainBundle:
    """What ``reference.reference_round`` needs of a bundle."""

    def __init__(self, config: dict):
        self.config = config

    def apply_train(self, variables, x, rng=None):
        return forward(self.config, variables["params"], x), variables
