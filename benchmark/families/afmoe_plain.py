"""The plain reference of the ``afmoe`` family: the forward pass of an
AFMoE-style decoder (Trinity-Mini's block) written out in ``jax.numpy`` and
float32 from the equations, over the program's own parameter tree.

No kernel, no ``vmap``, no grouped product, no code of ``fedml_tpu``.  With
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` and no bias in any projection:

- **Embedding**: ``x0 = E[token] * sqrt(hidden_size)`` where the configuration
  says ``mup_enabled``; the factor is part of the function.
- **A layer**, a norm on both sides of each sublayer:
  ``x = x + RMSNorm_post_attn(attn(RMSNorm_in(x)))``, then
  ``x = x + RMSNorm_post_mlp(mlp(RMSNorm_pre_mlp(x)))``.
- **Gated attention**, ``a`` the normed input: ``q = a Wq`` a head,
  ``k = a Wk``, ``v = a Wv`` a k/v head, ``z = a Wz`` a q head (the gate's
  projection); ``q`` and ``k`` RMS-normed over a head's channels, one weight
  for q and one for k; **on a ``sliding_attention`` layer** rotary positions on
  every channel of q and k, rotate-half pairs ``(i, i + d / 2)``, tables of
  this file's own from ``rope_theta``, and query ``t`` sees keys ``s`` with
  ``0 <= t - s < sliding_window``; **on a ``full_attention`` layer no
  positional encoding at all** and ``t`` sees every ``s <= t``; explicit
  scores over ``sqrt(d)`` with the window and the causal rule as index
  arithmetic, a softmax, q head ``i`` reading k/v head ``i // (Hq / Hkv)``;
  **``y = (concat_i(o_i) * sigmoid(z)) Wo``**.
- **Dense MLP** (the leading ``num_dense_layers``):
  ``(silu(b Wg) * (b Wu)) Wd``.
- **Expert layer**: ``s = sigmoid(b Wr)`` over all routed experts; **its own
  choice**: a stable ``argsort`` of ``-(s + beta)`` (``beta`` the selection
  bias, under ``stop_gradient``) puts a token's experts in order, equal sums by
  expert id, and the first ``num_experts_per_tok`` are the set ``S``;
  ``w_e = route_scale * s_e / (sum_{j in S} s_j + 1e-20)`` from the scores
  **without** the bias; a Python loop over the experts held, each applied to
  every token and weighted by ``w_e`` or 0; the shared expert beside it passes
  every token.  What the absent experts would add is left out, as in the
  program.
- A final RMSNorm, an untied head.

Scores run in query blocks of ``Q_BLOCK``, a block after another under
``lax.map``, a k/v head's q heads at a time, and every layer under
``jax.checkpoint``, so that 8192 positions fit beside
``reference.reference_round``'s copies of the parameters.  Matmul precision is
the caller's (``reference_round`` sets ``highest``).

**Assumed** (the catalog row has no key for them; the configuration file's
``assumed`` gives each with its reason): positions by layer kind, the gate,
the four norms and the q/k norms (the ``afmoe`` block as its public modelling
code has it); ``mup_enabled`` acting on the embedding's output alone (no factor
on logits or on the attention scale); the q/k norms' ``eps`` the layer's;
initial weights (embeddings of std ``1 / sqrt(hidden_size)``, ``beta`` normal
from the seed); ``n_positions``; the learning rate.  **Departures**: the
balance rule that moves ``beta`` between steps is not built (``beta`` is
frozen: the loss gives it exactly zero, as in a fine-tune through the public
code, where it is a buffer); no auxiliary loss; weights random from the seed.
"""

from __future__ import annotations

import math

import numpy as np

# queries a block of explicit scores: [8 q heads, 2048, 8192] float32 is 512
# MiB, a k/v head's share of a layer's scores
Q_BLOCK = 2048
SLIDING, FULL = "sliding_attention", "full_attention"


def layer_kinds(config: dict) -> list:
    """[(mixer, mlp)] a layer: SLIDING or FULL from ``layer_types`` read from
    the front, "dense" for the leading ``num_dense_layers`` and "sparse"
    after."""
    kinds = config["layer_types"]
    return [(kinds[i % len(kinds)],
             "dense" if i < config["num_dense_layers"] else "sparse")
            for i in range(config["n_layer"])]


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def gated_mlp(b, p):
    import jax

    return (jax.nn.silu(b @ p["gate"]["kernel"]) * (b @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def router(config: dict, b, p):
    """b [T, h] -> (weights [T, routed], zero off the chosen; chosen ids
    [T, top_k], the bias's choice)."""
    import jax
    import jax.numpy as jnp

    routed = config.get("num_experts_routed", config["num_experts"])
    score = jax.nn.sigmoid(b @ p["router"])
    shifted = score + jax.lax.stop_gradient(p["selection_bias"])
    chosen = jnp.argsort(-shifted, axis=-1, stable=True)[
        :, :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(score, chosen, axis=-1)  # without the bias
    if config["route_norm"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * config["route_scale"]
    weight = jnp.zeros((b.shape[0], routed), score.dtype)
    return weight.at[jnp.arange(b.shape[0])[:, None], chosen].set(
        picked), chosen


def expert_layer(config: dict, b, p, shared=None):
    """b [T, h] -> (the held experts' part of the routed sum, plus the shared
    expert where ``shared`` holds one, [T, h]; chosen ids [T, top_k])."""
    import jax
    import jax.numpy as jnp

    held = list(config.get("experts_held", range(config["num_experts"])))
    weight, chosen = router(config, b, p)
    y = jnp.zeros_like(b)
    for slot, e in enumerate(held):
        f = (jax.nn.silu(b @ p["gate"][slot]) * (b @ p["up"][slot])) \
            @ p["down"][slot]
        y = y + weight[:, e:e + 1] * f
    if shared is not None:
        y = y + gated_mlp(b, shared)
    return y, chosen


def attention(config: dict, a, p, kind: str):
    """The gated attention of one sequence: a [L, h], the normed input."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    L = a.shape[0]
    pos = jnp.arange(L)
    window = config["sliding_window"] if kind == SLIDING else None

    def rotate(x):  # [L, H, d], every channel, pairs (i, i + d / 2)
        freq = config["rope_theta"] ** (-2.0 * np.arange(d // 2) / d)
        angle = pos.astype(f32)[:, None] * jnp.asarray(freq, f32)[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return (x * cos.astype(x.dtype)
                + jnp.concatenate([-x2, x1], axis=-1) * sin.astype(x.dtype))

    @jax.checkpoint
    def attend_block(q, k, v, qpos):
        """q [bq, rep, d] of the q heads that share the k/v head k, v [L, d],
        at positions qpos."""
        s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(d)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (qpos[:, None] - pos[None, :] < window)
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->qhd", prob, v)

    qkv = (a @ p["Dense_0"]["kernel"]).reshape(L, n_q + 2 * n_kv, d)
    q, k, v = qkv[:, :n_q], qkv[:, n_q:n_q + n_kv], qkv[:, n_q + n_kv:]
    z = a @ p["gate"]["kernel"]  # [L, n_q * d]
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if kind == SLIDING:  # a full layer has no positional encoding
        q, k = rotate(q), rotate(k)
    rep = n_q // n_kv
    size = Q_BLOCK if L % Q_BLOCK == 0 else L

    def block(start):
        rows = jax.lax.dynamic_slice_in_dim(q, start, size)
        return jnp.concatenate([
            attend_block(rows[:, g * rep:(g + 1) * rep], k[:, g], v[:, g],
                         start + jnp.arange(size)) for g in range(n_kv)],
            axis=1)

    o = jax.lax.map(block, jnp.arange(0, L, size)).reshape(L, n_q * d)
    return (o * jax.nn.sigmoid(z)) @ p["Dense_1"]["kernel"]


def forward(config: dict, params, ids, with_selection: bool = False,
            dtype: str = "float32"):
    """Logits [B, L, V] of token ids [B, L]; ``with_selection`` also returns
    every expert layer's chosen expert ids [B * L, top_k].  Everything is
    computed in ``dtype``: float32 is the reference, and a lower one is the
    control that says what the check's limits can tell apart
    (``tools/control_afmoe.py``)."""
    import jax
    import jax.numpy as jnp

    eps = config["rms_norm_eps"]

    def layer(x, p, kind, mlp):
        a = rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        mixed = jnp.stack([
            attention(config, a[i], p["MultiHeadAttention_0"], kind)
            for i in range(x.shape[0])])
        x = x + rms_norm(mixed, p["post_attn_norm"]["scale"], eps)
        b = rms_norm(x, p["RMSNorm_1"]["scale"], eps).reshape(-1, x.shape[-1])
        if mlp == "dense":
            y, chosen = gated_mlp(b, p["mlp"]), None
        else:
            y, chosen = expert_layer(
                config, b, p["ExpertLayer_0"],
                p["shared_expert"] if config["num_shared_experts"] else None)
        y = rms_norm(y.reshape(x.shape), p["post_mlp_norm"]["scale"], eps)
        return x + y, chosen

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    x = params["wte"]["embedding"][ids]
    if config.get("mup_enabled"):
        x = x * math.sqrt(config["hidden_size"])
    selection = []
    for i, (kind, mlp) in enumerate(layer_kinds(config)):
        x, chosen = jax.checkpoint(
            lambda x, p, kind=kind, mlp=mlp: layer(x, p, kind, mlp))(
            x, params[f"Block_{i}"])
        if chosen is not None:
            selection.append(chosen)
    logits = rms_norm(x, params["norm_f"]["scale"], eps) \
        @ params["lm_head"]["kernel"]
    return (logits, selection) if with_selection else logits


class PlainBundle:
    """What ``reference.reference_round`` needs of a bundle."""

    def __init__(self, config: dict, dtype: str = "float32"):
        self.config, self.dtype = config, dtype

    def apply_train(self, variables, x, rng=None):
        return forward(self.config, variables["params"], x,
                       dtype=self.dtype), variables
