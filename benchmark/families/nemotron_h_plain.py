"""The plain reference of the ``nemotron_h`` family: the forward pass of a
Nemotron-H-style hybrid decoder (Nemotron-3-Super's layers) written out in
``jax.numpy`` and float32 from the equations, over the program's own
parameter tree.

No kernel, no ``vmap``, no chunked form, no grouped product, no code of
``fedml_tpu``.  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``, no bias in
any projection, no positional encoding anywhere.  Every layer is one pre-norm
residual sublayer, ``x = x + f(RMSNorm(x))``, and ``f`` is, by the layer's
character in ``hybrid_override_pattern``:

- ``M``, **the Mamba-2 mixer**, ``a`` the normed input of one sequence:
  ``[z | xBC | dt] = a W_in``; ``xBC = silu(conv(xBC) + b)`` with ``conv`` a
  causal depthwise convolution written as its shifted multiply-adds;
  ``[x | B | C] = xBC``, heads of ``mamba_head_dim``, head ``h`` reading
  group ``h // (H / G)`` of B and C; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; then **the recurrence, a token at a time**:
  ``S = exp(dt_t A) S + dt_t B_t x_t^T``, ``y_t = S^T C_t + D x_t``; ``y``
  times ``silu(z)``, RMS-normed by groups of ``H P / G`` channels, times its
  weight, and projected out.  The recurrence is a ``lax.scan`` over the tokens
  in segments of ``SEGMENT`` under ``jax.checkpoint``: a backward keeps one
  state a segment;
- ``*``, **causal softmax attention**: ``q``, ``k``, ``v`` from one fused
  projection, q head ``i`` reading k/v head ``i // (Hq / Hkv)``, explicit
  scores over ``sqrt(head_dim)`` in query blocks of ``Q_BLOCK``, the causal
  rule as index arithmetic, no rotation;
- ``E``, **the latent expert layer**: ``s = sigmoid(a Wr)`` over all routed
  experts; **its own choice**: a stable ``argsort`` of ``-(s + beta)``
  (``beta`` the frozen selection bias) puts a token's experts in order, equal
  sums by expert id, and the first ``num_experts_per_tok`` are the set ``S``;
  ``w_e = routed_scaling_factor * s_e / sum_{j in S} s_j`` from the scores
  **without** the bias; ``l = a W_down`` into ``moe_latent_size``; a Python
  loop over the experts held, each ``relu(l U_e)^2 D_e`` applied to every
  token and weighted by ``w_e`` or 0; the sum projected up by ``W_up``; plus
  the shared expert ``relu(a S_up)^2 S_down`` at full width.  What the absent
  experts would add is left out, as in the program;
- ``-``, a dense ``relu(a U)^2 D``.

A final RMSNorm and an untied head.  Every layer runs under
``jax.checkpoint``.  Matmul precision is the caller's
(``reference.reference_round`` sets ``highest``).  Assumptions and departures
from the published model: the configuration file's ``assumed`` and
``departures``.
"""

from __future__ import annotations

import math

from benchmark.families.afmoe_plain import rms_norm
from benchmark.families.kimi_linear_plain import conv

Q_BLOCK = 2048  # queries a block of explicit scores
SEGMENT = 64  # tokens of the recurrence between two kept states
MIXER = {"M": "mamba", "*": "attention", "E": None, "-": None}
MLP = {"M": None, "*": None, "E": "sparse", "-": "dense"}


def layer_kinds(config: dict) -> list:
    """[(mixer, mlp)] a layer, read from the front of
    ``hybrid_override_pattern``: ("mamba", None), ("attention", None),
    (None, "sparse") or (None, "dense")."""
    pattern = config["hybrid_override_pattern"]
    return [(MIXER[ch], MLP[ch]) for ch in pattern[:config["n_layer"]]]


def relu2(t):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(t, 0))


def ssd(x, dt, A, B, C):
    """The recurrence: x [L, H, P], dt [L, H], A [H], B and C [L, G, N] ->
    y [L, H, P], without the skip."""
    import jax
    import jax.numpy as jnp

    L, H, P = x.shape
    rep = H // B.shape[1]
    seg = SEGMENT if L % SEGMENT == 0 else L

    def token(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        B_h, C_h = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * B_h)[:, :, None] * x_t[:, None, :])
        return S, jnp.einsum("hnp,hn->hp", S, C_h)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(t.reshape(L // seg, seg, *t.shape[1:]) for t in (x, dt, B, C))
    S0 = jnp.zeros((H, B.shape[-1], P), x.dtype)
    return jax.lax.scan(segment, S0, xs)[1].reshape(L, H, P)


def mamba(config: dict, a, p):
    """The Mamba-2 mixer of one sequence: a [L, h], the normed input."""
    import jax
    import jax.numpy as jnp

    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    d, L = H * P, a.shape[0]
    proj = a @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :d], proj[:, d:2 * d + 2 * G * N], proj[:, -H:]
    xbc = conv(xbc, p["conv"])
    if config["use_conv_bias"]:
        xbc = xbc + p["conv_bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d].reshape(L, H, P)
    B = xbc[:, d:d + G * N].reshape(L, G, N)
    C = xbc[:, d + G * N:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(x, dt, -jnp.exp(p["A_log"]), B, C) + p["D"][:, None] * x
    y = (y.reshape(L, d) * jax.nn.silu(z)).reshape(L, G, d // G)
    y = rms_norm(y, 1.0, config["layer_norm_epsilon"]).reshape(L, d) \
        * p["norm"]
    return y @ p["out_proj"]["kernel"]


def attention(config: dict, a, p):
    """Causal attention of one sequence with no positions: a [L, h]."""
    import jax
    import jax.numpy as jnp

    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, L = config["head_dim"], a.shape[0]
    pos = jnp.arange(L)

    @jax.checkpoint
    def attend_block(q, k, v, qpos):
        """q [bq, rep, d] of the q heads that share the k/v head k, v [L, d],
        at positions qpos."""
        s = jnp.einsum("qhd,kd->hqk", q, k) / math.sqrt(d)
        seen = pos[None, :] <= qpos[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->qhd", prob, v)

    qkv = (a @ p["Dense_0"]["kernel"]).reshape(L, n_q + 2 * n_kv, d)
    q, k, v = qkv[:, :n_q], qkv[:, n_q:n_q + n_kv], qkv[:, n_q + n_kv:]
    rep = n_q // n_kv
    out = [jnp.concatenate([
        attend_block(q[i:i + Q_BLOCK, g * rep:(g + 1) * rep], k[:, g],
                     v[:, g], pos[i:i + Q_BLOCK]) for g in range(n_kv)],
        axis=1) for i in range(0, L, Q_BLOCK)]
    return jnp.concatenate(out, axis=0).reshape(L, n_q * d) \
        @ p["Dense_1"]["kernel"]


def router(config: dict, b, p):
    """b [T, h] -> (weights [T, routed], zero off the chosen; chosen ids
    [T, top_k], the bias's choice)."""
    import jax
    import jax.numpy as jnp

    routed = config["n_routed_experts"]
    score = jax.nn.sigmoid(b @ p["router"])
    shifted = score + jax.lax.stop_gradient(p["selection_bias"])
    chosen = jnp.argsort(-shifted, axis=-1, stable=True)[
        :, :config["num_experts_per_tok"]]
    picked = jnp.take_along_axis(score, chosen, axis=-1)  # without the bias
    if config["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    picked = picked * config["routed_scaling_factor"]
    weight = jnp.zeros((b.shape[0], routed), score.dtype)
    return weight.at[jnp.arange(b.shape[0])[:, None], chosen].set(
        picked), chosen


def expert_layer(config: dict, b, p, shared=None):
    """b [T, h] -> (the held experts' part of the routed sum, in the latent
    width and projected up, plus the shared expert where ``shared`` holds
    one, [T, h]; chosen ids [T, top_k])."""
    import jax.numpy as jnp

    held = list(config["experts_held"])
    weight, chosen = router(config, b, p)
    latent = b @ p["latent_down"]
    y = jnp.zeros_like(latent)
    for slot, e in enumerate(held):
        y = y + weight[:, e:e + 1] * (relu2(latent @ p["up"][slot])
                                      @ p["down"][slot])
    y = y @ p["latent_up"]
    if shared is not None:
        y = y + relu2(b @ shared["up"]["kernel"]) @ shared["down"]["kernel"]
    return y, chosen


def forward(config: dict, params, ids, with_selection: bool = False,
            dtype: str = "float32"):
    """Logits [B, L, V] of token ids [B, L]; ``with_selection`` also returns
    every expert layer's chosen expert ids [B * L, top_k].  Everything is
    computed in ``dtype``: float32 is the reference, and a lower one is the
    control that says what the check's limits can tell apart
    (``tools/control_nemotron_h.py``)."""
    import jax
    import jax.numpy as jnp

    eps = config["layer_norm_epsilon"]

    def layer(x, p, mixer, mlp):
        a = rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        if mixer == "mamba":
            return x + jnp.stack([mamba(config, a[i], p["Mamba2Mixer_0"])
                                  for i in range(x.shape[0])]), None
        if mixer == "attention":
            return x + jnp.stack([
                attention(config, a[i], p["MultiHeadAttention_0"])
                for i in range(x.shape[0])]), None
        b = a.reshape(-1, x.shape[-1])
        if mlp == "dense":
            y, chosen = relu2(b @ p["mlp"]["up"]["kernel"]) \
                @ p["mlp"]["down"]["kernel"], None
        else:
            y, chosen = expert_layer(config, b, p["ExpertLayer_0"],
                                     p.get("shared_expert"))
        return x + y.reshape(x.shape), chosen

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    x = params["wte"]["embedding"][ids]
    selection = []
    for i, (mixer, mlp) in enumerate(layer_kinds(config)):
        x, chosen = jax.checkpoint(
            lambda x, p, mixer=mixer, mlp=mlp: layer(x, p, mixer, mlp))(
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
