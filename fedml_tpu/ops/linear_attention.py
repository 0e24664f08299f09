"""The gated delta rule with a per-channel decay: linear attention whose
state is carried along the sequence.

For one head, a state ``S`` of [d_k, d_v] starts at zero and every token
decays it channel by channel, corrects what it stores under the token's key
towards the token's value, and reads it with the token's query:

    S~  = Diag(exp(g_t)) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

``g`` is the log of the decay, ``<= 0``.  ``gated_delta_rule_recurrent`` is
that definition as a ``lax.scan`` a token.  ``gated_delta_rule`` computes the
same in chunks of ``chunk`` tokens, with matmuls: inside a chunk entered with
state ``S0``, with ``G_r = sum_{i<=r} g_i`` (per channel),

    A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)          (i < r)
    U = (I + A)^-1 (beta * V),   W = (I + A)^-1 (beta * K * exp(G))
    N = U - W S0
    o_r = S0^T (q_r * exp(G_r)) + sum_{i<=r} [sum_c q_rc k_ic exp(G_rc - G_ic)] N_i
    S_C = Diag(exp(G_C)) S0 + sum_i (k_i * exp(G_C - G_i)) N_i^T

(substitute ``S_r = Diag(exp(G_r)) S0 + sum_{i<=r} (k_i * exp(G_r - G_i))
N_i^T`` into the recurrence: row ``r`` of ``N`` is ``beta_r (v_r - S~^T k_r)``,
which gives ``(I + A) N = beta * V - (beta * K * exp(G)) S0``.)  What does not
depend on ``S0`` (``A``, the solve, the query-key products) is computed for
every chunk at once; a ``lax.scan`` over the chunks carries ``S`` through three
products a chunk.  JAX differentiates both forms.

**No ``exp(-G)``.**  A channel may decay by ``exp(-10)`` a token, so over a
chunk ``exp(-G)`` leaves float32 (``exp(88)``) where ``exp(G_r - G_i)`` for
``i <= r`` never exceeds 1.  The pair sums are therefore taken in sub-blocks
of ``SUB`` rows: a pair inside one sub-block uses the difference itself (a
[SUB, SUB, d] block, summed over channels); a pair across sub-blocks is split
at the row's sub-block start ``m``, ``exp(G_r - G_m) exp(G_m - G_i)`` with
both exponents ``<= 0``, and is a matmul.  Decays, cumulative sums, the pair
sums and the triangular solve are float32 whatever the inputs' dtype; the
products with the state take the inputs' dtype as operands and add in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SUB = 16  # rows of a sub-block: its pair sums are taken over explicit differences
_HIGHEST = jax.lax.Precision.HIGHEST


def short_causal_conv(u, taps):
    """A causal depthwise convolution over the sequence: ``y_t = sum_j
    taps[j] * u_{t - (K - 1) + j}`` with zeros before the sequence starts.
    ``u`` [..., L, channels], ``taps`` [K, channels], one filter a channel."""
    K, L = taps.shape[0], u.shape[-2]
    padded = jnp.pad(u, ((0, 0),) * (u.ndim - 2) + ((K - 1, 0), (0, 0)))
    return sum(taps[j] * jax.lax.slice_in_dim(padded, j, j + L, axis=-2)
               for j in range(K))


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The definition, a token at a time.  ``q``, ``k``, ``g`` [L, H, d_k],
    ``v`` [L, H, d_v], ``beta`` [L, H]; returns ``o`` [L, H, d_v] in float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))

    def token(S, x):
        q_t, k_t, v_t, g_t, beta_t = x
        S = jnp.exp(g_t)[..., None] * S
        stored = jnp.einsum("hd,hde->he", k_t, S, precision=_HIGHEST)
        S = S + (beta_t[:, None] * k_t)[..., None] * (v_t - stored)[:, None, :]
        return S, jnp.einsum("hd,hde->he", q_t, S, precision=_HIGHEST)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), f32)
    return jax.lax.scan(token, S0, (q, k, v, g, beta))[1]


def _cumulative(g):
    """(``G_r = sum_{i<=r} g_i`` over the chunk [..., C, d], the same sums
    restarted at every sub-block [..., nb, sub, d]).  A difference of two
    rows of one sub-block is taken from the second: the chunk's sums grow
    large where an earlier token decayed much, and their difference would
    round at that size."""
    *lead, C, d = g.shape
    sub = min(SUB, C)
    local = jnp.cumsum(g.reshape(*lead, C // sub, sub, d), axis=-2)
    before = jnp.cumsum(local[..., -1:, :], axis=-3) - local[..., -1:, :]
    return (local + before).reshape(g.shape), local


@jax.checkpoint
def _decayed_pairs(x, y, G, local):
    """``M[j, .., r, i] = sum_c x_jrc y_ic exp(G_rc - G_ic)`` for ``i <= r``, 0
    elsewhere.  ``x`` [J, ..., C, d] (several row operands against one ``y``:
    the decays are exponentiated once), ``y`` [..., C, d] float32, ``G`` and
    ``local`` from ``_cumulative``; ``C`` a multiple of ``SUB`` or shorter
    than it.  Under ``jax.checkpoint``: a backward keeps the operands, not
    the [SUB, SUB, d] blocks."""
    *lead, C, d = y.shape
    nb, sub = local.shape[-3:-1]
    xb, yb = x.reshape(-1, *local.shape), y.reshape(local.shape)
    row = jnp.arange(sub)
    # inside a sub-block: the differences themselves, masked before the exp
    diff = jnp.where((row[:, None] >= row[None, :])[:, :, None],
                     local[..., :, None, :] - local[..., None, :, :], -jnp.inf)
    inside = (xb[..., :, None, :] * (yb[..., None, :, :] * jnp.exp(diff))
              ).sum(axis=-1)                                 # [J, .., nb, s, s]
    if nb == 1:
        return inside.reshape(-1, *lead, C, C)
    # across sub-blocks: split at the row block's first row, m
    x_m = xb * jnp.exp(local - local[..., :1, :])               # G_r - G_m <= 0
    start = G.reshape(local.shape)[..., :1, :]                  # [.., nb, 1, d]
    earlier = (jnp.arange(C)[None, :] < (jnp.arange(nb) * sub)[:, None])
    since = jnp.where(earlier[:, :, None],
                      start - G[..., None, :, :], -jnp.inf)     # G_m - G_i <= 0
    y_m = y[..., None, :, :] * jnp.exp(since)                   # [.., nb, C, d]
    across = jnp.einsum("j...bsd,...bid->j...bsi", x_m, y_m,
                        precision=_HIGHEST)                  # [J, .., nb, s, C]
    across = across.reshape(-1, *lead, nb, sub, nb, sub)
    same = jnp.eye(nb, dtype=y.dtype)[:, None, :, None]
    return (across + inside[..., :, :, None, :] * same).reshape(
        -1, *lead, C, C)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``gated_delta_rule_recurrent`` in chunks of ``chunk`` tokens (a
    multiple of ``SUB``, or less than it): the same ``o`` [L, H, d_v], in the
    dtype of ``v``.  A length that ``chunk`` does not divide is padded with
    tokens that store nothing and decay nothing."""
    if chunk > SUB and chunk % SUB:
        raise ValueError(f"chunk {chunk} is no multiple of {SUB}")
    L, H, dk = q.shape
    dv, f32, dtype = v.shape[-1], jnp.float32, v.dtype
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(t):  # [L, H, ...] -> [n, H, C, ...]
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return jnp.moveaxis(t.reshape(n, chunk, *t.shape[1:]), 1, 2)

    qc, kc, vc, gc = (chunks(t.astype(f32)) for t in (q, k, v, g))
    bc = chunks(beta.astype(f32))[..., None]                    # [n, H, C, 1]
    G, local = _cumulative(gc)
    kk, P = _decayed_pairs(jnp.stack([kc, qc]), kc, G, local)   # [n, H, C, C]
    A = bc * jnp.tril(kk, -1)
    decayed = jnp.exp(G)
    solved = jax.lax.linalg.triangular_solve(
        A + jnp.eye(chunk, dtype=f32),
        jnp.concatenate([bc * vc, bc * kc * decayed], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    U, W = solved[..., :dv], solved[..., dv:]
    q_in = qc * decayed                                         # reads S0
    k_out = kc * jnp.exp(G[:, :, -1:, :] - G)                   # writes S_C
    kept = decayed[:, :, -1, :, None]                           # [n, H, d_k, 1]

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    def one_chunk(S, x):
        U, W, P, q_in, k_out, kept = x
        N = U - dot("hcd,hde->hce", W, S)
        o = dot("hcd,hde->hce", q_in, S) + dot("hci,hie->hce", P, N)
        return kept * S + dot("hcd,hce->hde", k_out, N), o

    _, o = jax.lax.scan(one_chunk, jnp.zeros((H, dk, dv), f32),
                        (U, W, P, q_in, k_out, kept))
    return jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, dv)[:L].astype(dtype)
