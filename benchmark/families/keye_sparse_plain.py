"""The plain reference of the ``keye_sparse`` family: the forward pass of a
decoder whose attention sees only the keys a learned indexer picks, written
out in ``jax.numpy`` and float32 from the equations, over the program's own
parameter tree.

No kernel, no ``vmap``, no grouped product, no code of ``fedml_tpu``.  A layer:
RMSNorm; q, k, v projections, an RMSNorm over each q and k head's channels,
rotate-half rotary positions on all of them; the indexer, ``qI = a WqI`` (16
heads of 64), ``kI = LayerNorm(a WkI)`` (one index key a token), ``w = a Ww``,
``qI`` and ``kI`` rotated, index scores ``I[t, s] = sum_j w[t, j] relu(qI[t,
j] . kI[s])`` over the causal pairs, explicit, an index head at a time; **its
own choice**: a stable ``argsort`` of the negated masked scores puts a row's
positions in order, equal scores by position, and the first ``topk`` of them
that are causal are the set; attention as explicit scores with a ``where`` of
the chosen pairs and a softmax, every k/v head serving ``Hq / Hkv`` q heads;
then the expert layer of ``mellum_moe_plain`` (softmax router over all routed
experts, its own top-k, a Python loop over the experts held).  The discrete
choice passes no gradient: the indexer's leaves get exactly zero.

Scores run in query blocks, a block after another under ``lax.map`` (and
attention a k/v head at a time inside one), and every layer under
``jax.checkpoint``, so that 8192 positions fit beside
``reference.reference_round``'s copies of the parameters.  Matmul precision is
the caller's (``reference_round`` sets ``highest``).

Assumptions and departures from the published model: the configuration file's
``assumed`` and ``departures``.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.families.mellum_moe_plain import expert_layer

# queries a block of explicit scores: [8 q heads, 2048, 8192] float32 is 512
# MiB, a k/v head's share of the attention scores
Q_BLOCK = 2048


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def hidden(config: dict, params, ids):
    """(the last layer's output [B, L, h] float32 of token ids [B, L], before
    the final norm and the head; every layer's chosen pairs [B, L, L],
    bool)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = config["rms_norm_eps"]
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, theta = config["head_dim"], config["rope_theta"]
    sa = config["sa_config"]
    n_i, d_i, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                      sa["topk"])
    L = ids.shape[1]
    pos = jnp.arange(L)

    def rms(x, g):
        return rms_norm(x, g, eps)

    def layer_norm(x, p):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]

    def rotate(x):  # [L, H, width], every channel, pairs (i, i + width / 2)
        width = x.shape[-1]
        freq = theta ** (-2.0 * np.arange(width // 2) / width)
        angle = pos.astype(f32)[:, None] * jnp.asarray(freq, f32)[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
        x1, x2 = x[..., : width // 2], x[..., width // 2:]
        return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin

    @jax.checkpoint
    def choose_block(q_i, k_i, w, qpos):
        """Chosen pairs [bq, L] of the queries at ``qpos``: q_i [bq, n_i,
        d_i], w [bq, n_i] against every index key k_i [L, d_i]."""
        score = jnp.zeros((q_i.shape[0], L), f32)
        for j in range(n_i):
            score = score + w[:, j:j + 1] * jax.nn.relu(q_i[:, j] @ k_i.T)
        causal = pos[None, :] <= qpos[:, None]
        order = jnp.argsort(-jnp.where(causal, score, -jnp.inf), axis=-1,
                            stable=True)
        rank = jnp.argsort(order, axis=-1)  # a position's place in its row
        return (rank < topk) & causal

    @jax.checkpoint
    def attend_block(q, k, v, chosen):
        """q [bq, rep, d] of the q heads that share the k/v head k, v [L, d],
        over the chosen pairs [bq, L]."""
        s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->qhd", p, v)

    def attention(a, p):
        """One sequence: a [L, h] -> (the layer's output, the chosen pairs)."""
        qkv = (a @ p["Dense_0"]["kernel"]).reshape(L, n_q + 2 * n_kv, d)
        q, k, v = (qkv[:, :n_q], qkv[:, n_q:n_q + n_kv], qkv[:, n_q + n_kv:])
        q = rotate(rms(q, p["q_norm"]["scale"]))
        k = rotate(rms(k, p["k_norm"]["scale"]))
        ix = p["indexer"]
        q_i = rotate((a @ ix["q"]["kernel"]).reshape(L, n_i, d_i))
        k_i = rotate(layer_norm(a @ ix["k"]["kernel"], ix["k_norm"])[:, None]
                     )[:, 0]
        w = a @ ix["w"]["kernel"]
        rep = n_q // n_kv
        size = Q_BLOCK if L % Q_BLOCK == 0 else L

        def block(start):
            """The queries ``start .. start + size``: (their output [size,
            n_q, d], their chosen pairs [size, L])."""
            def rows(t):
                return jax.lax.dynamic_slice_in_dim(t, start, size)

            chosen = jax.lax.stop_gradient(choose_block(
                rows(q_i), k_i, rows(w), start + jnp.arange(size)))
            return jnp.concatenate([
                attend_block(rows(q)[:, g * rep:(g + 1) * rep], k[:, g],
                             v[:, g], chosen) for g in range(n_kv)],
                axis=1), chosen

        # one block's code, run a block after another: the compiled step
        # holds one sort a layer and direction, not one a block
        o, sets = jax.lax.map(block, jnp.arange(0, L, size))
        return (o.reshape(L, n_q * d) @ p["Dense_1"]["kernel"],
                sets.reshape(L, L))

    def layer(x, p):
        a = rms(x, p["RMSNorm_0"]["scale"])
        mixed = [attention(a[i], p["MultiHeadAttention_0"])
                 for i in range(x.shape[0])]
        x = x + jnp.stack([y for y, _ in mixed])
        b = rms(x, p["RMSNorm_1"]["scale"])
        y, _ = expert_layer(config, b.reshape(-1, b.shape[-1]),
                            p["ExpertLayer_0"])
        return x + y.reshape(x.shape), jnp.stack([c for _, c in mixed])

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
    x = params["wte"]["embedding"][ids]
    selection = []
    for i in range(config["n_layer"]):
        x, chosen = jax.checkpoint(layer)(x, params[f"Block_{i}"])
        selection.append(chosen)
    return x, selection


def forward(config: dict, params, ids, with_selection: bool = False):
    """Logits [B, L, V] float32 of token ids [B, L]; ``with_selection`` also
    returns every layer's chosen pairs [B, L, L] (bool)."""
    import jax.numpy as jnp

    x, selection = hidden(config, params, ids)
    scale, head = (jnp.asarray(a, jnp.float32) for a in (
        params["norm_f"]["scale"], params["lm_head"]["kernel"]))
    logits = rms_norm(x, scale, config["rms_norm_eps"]) @ head
    return (logits, selection) if with_selection else logits


class PlainBundle:
    """What ``reference.reference_round`` needs of a bundle."""

    def __init__(self, config: dict):
        self.config = config

    def apply_train(self, variables, x, rng=None):
        return forward(self.config, variables["params"], x), variables
