"""The plain reference of the ``mellum_moe`` family: the forward pass of a
Mellum-style decoder written out in ``jax.numpy`` and float32 from the
published equations, over the program's own parameter tree.

No kernel, no ``vmap``, no grouped product, no code of ``fedml_tpu``: RMSNorm,
rotate-half rotary positions (plain on sliding layers, YaRN on full ones),
attention as explicit scores and a softmax with the causal and the window
mask, every k/v head serving ``Hq / Hkv`` q heads, a softmax router over all
routed experts with its own top-k selection, and a Python loop over the experts
held, each applied to every token and weighted by its routing weight or 0.
What the absent experts would add is left out, as in the program.

Attention runs in query blocks and every layer under ``jax.checkpoint``, so
that 8192 positions fit beside ``reference.reference_round``'s copies of the
parameters.  Matmul precision is the caller's (``reference_round`` sets
``highest``).

Departures from the published model, as in the configuration file: no MTP
head; no q/k normalisation and no auxiliary balance loss (the config has no
key for either); ``layer_types`` governs and ``max_window_layers`` is not
read; a query at ``i`` sees keys ``j <= i`` with ``i - j < sliding_window``.
"""

from __future__ import annotations

import functools
import math

import numpy as np

# queries a block of explicit scores: at 8192 positions and 4 q heads a block
# is 256 MiB of float32, and the four blocks a layer compile in half the time
# of sixteen (the reference's one compiled step is most of a run's check)
Q_BLOCK = 2048


def inv_freq(rope: dict, head_dim: int):
    """(inverse frequencies [head_dim / 2], factor on cos and sin)."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    base = rope["rope_theta"] ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return base, 1.0
    theta, orig = rope["rope_theta"], rope["original_max_position_embeddings"]
    low = math.floor(head_dim * math.log(orig / (rope["beta_fast"] * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(head_dim * math.log(orig / (rope["beta_slow"] * 2 * math.pi))
                     / (2 * math.log(theta)))
    low, high = max(low, 0), min(high, head_dim - 1)
    m = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return (base * m + base / rope["factor"] * (1.0 - m),
            rope.get("attention_factor", 0.1 * math.log(rope["factor"]) + 1.0))


def layer_types(config: dict) -> list:
    kinds = config["layer_types"]
    return [kinds[i % len(kinds)] for i in range(config["n_layer"])]


def expert_layer(config: dict, b, p):
    """b [T, h] -> (the held experts' part of the layer's output [T, h], the
    chosen expert ids [T, top_k]); ``p`` holds ``router`` [h, routed] and
    ``gate``, ``up``, ``down`` stacked over the experts held."""
    import jax
    import jax.numpy as jnp

    held = list(config.get("experts_held", range(config["num_experts"])))
    routed = config.get("num_experts_routed", config["num_experts"])
    prob = jax.nn.softmax(b @ p["router"], axis=-1)  # over all routed
    chosen = jnp.argsort(-prob, axis=-1)[:, :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        picked = picked / picked.sum(axis=-1, keepdims=True)
    weight = jnp.zeros((b.shape[0], routed), jnp.float32)
    weight = weight.at[jnp.arange(b.shape[0])[:, None], chosen].set(picked)
    y = jnp.zeros_like(b)
    for slot, e in enumerate(held):
        f = (jax.nn.silu(b @ p["gate"][slot]) * (b @ p["up"][slot])) \
            @ p["down"][slot]
        y = y + weight[:, e:e + 1] * f
    return y, chosen


def forward(config: dict, params, ids, with_selection: bool = False):
    """Logits [B, L, V] float32 of token ids [B, L]; ``with_selection`` also
    returns every layer's chosen expert ids [B * L, top_k]."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = config["rms_norm_eps"]
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    L = ids.shape[1]
    pos = jnp.arange(L)

    def rms(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g

    def rotate(x, cos, sin):  # [L, H, d]
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * cos[:, None, :] + rot * sin[:, None, :]

    @functools.partial(jax.checkpoint, static_argnums=(4,))
    def attend_block(q, k, v, qpos, window):
        """q [bq, Hq, d] at positions qpos against all of k, v [L, Hkv, d]."""
        k = jnp.repeat(k, n_q // n_kv, axis=1)
        v = jnp.repeat(v, n_q // n_kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (qpos[:, None] - pos[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    def attention(a, w_qkv, w_o, kind):
        """One sequence: a [L, h]."""
        qkv = (a @ w_qkv).reshape(L, n_q + 2 * n_kv, d)
        q, k, v = (qkv[:, :n_q], qkv[:, n_q:n_q + n_kv], qkv[:, n_q + n_kv:])
        freq, factor = inv_freq(config["rope_parameters"][kind], d)
        angle = pos.astype(f32)[:, None] * jnp.asarray(freq, f32)[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1) * factor
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1) * factor
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        window = config["sliding_window"] if kind == "sliding_attention" \
            else None
        out = [attend_block(q[i:i + Q_BLOCK], k, v, pos[i:i + Q_BLOCK], window)
               for i in range(0, L, Q_BLOCK)]
        return jnp.concatenate(out, axis=0).reshape(L, n_q * d) @ w_o

    def layer(x, p, kind):
        attn = p["MultiHeadAttention_0"]
        a = rms(x, p["RMSNorm_0"]["scale"])
        x = x + jnp.stack([
            attention(a[i], attn["Dense_0"]["kernel"],
                      attn["Dense_1"]["kernel"], kind)
            for i in range(x.shape[0])])
        b = rms(x, p["RMSNorm_1"]["scale"])
        y, chosen = expert_layer(config, b.reshape(-1, b.shape[-1]),
                                 p["ExpertLayer_0"])
        return x + y.reshape(x.shape), chosen

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
    x = params["wte"]["embedding"][ids]
    selection = []
    for i, kind in enumerate(layer_types(config)):
        x, chosen = jax.checkpoint(lambda x, p, kind=kind: layer(x, p, kind))(
            x, params[f"Block_{i}"])
        selection.append(chosen)
    logits = rms(x, params["norm_f"]["scale"]) @ params["lm_head"]["kernel"]
    return (logits, selection) if with_selection else logits


class PlainBundle:
    """What ``reference.reference_round`` needs of a bundle."""

    def __init__(self, config: dict):
        self.config = config

    def apply_train(self, variables, x, rng=None):
        return forward(self.config, variables["params"], x), variables
