"""The state-space recurrence of a Mamba-2 layer (SSD: a scalar decay a head).

For one head ``h`` of ``P`` channels, a state ``S`` of [N, P] starts at zero
and every token decays it by one scalar, adds the token's input under its
key ``B`` and is read by its query ``C``:

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t

``dt`` [L, H] > 0 (after its softplus), ``A`` [H] < 0, ``x`` [L, H, P],
``B`` and ``C`` [L, G, N]: head ``h`` reads group ``h // (H / G)``.  The
skip ``D x`` of the layer is not part of it.  ``ssd_recurrent`` is that
definition as a ``lax.scan`` a token.  ``ssd_chunked`` computes the same in
chunks of ``chunk`` tokens with matmuls: inside a chunk, with ``a = dt A`` and
``seg(r, i) = sum_{i<k<=r} a_k`` (never positive for ``i <= r``),

    y_r = sum_{i<=r} (C_r . B_i) exp(seg(r, i)) dt_i x_i
          + exp(seg(r, -1)) C_r^T S_0
    S_C = exp(seg(C-1, -1)) S_0 + sum_i exp(seg(C-1, i)) B_i (dt_i x_i)^T

where ``S_0`` is the state the chunk enters with.  Everything but ``S_0`` is
computed for every chunk at once; a ``lax.scan`` over the chunks carries the
[N, P] state, one multiply-add a chunk.  JAX differentiates both forms.

**No ``exp(-cumsum)``.**  A head may decay by ``exp(-10)`` a token, so the
exponent is always a sum of the decays between two tokens, taken as that
sum (a cumulative sum along the pair's own row, masked before the ``exp``),
never as the difference of two large cumulative sums nor as a product of
``exp(cumsum)`` and ``exp(-cumsum)``.  Decays, their sums and the decay masks
are float32 whatever the inputs' dtype; the products take ``x``'s dtype as
operands and add in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _by_head(t, heads: int):
    """[..., G, N] -> [..., H, N]: the group each head reads."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd_recurrent(x, dt, A, B, C):
    """The definition, a token at a time: ``y`` [L, H, P] in float32."""
    f32 = jnp.float32
    H = x.shape[1]
    x, dt, A = x.astype(f32), dt.astype(f32), A.astype(f32)
    B, C = _by_head(B.astype(f32), H), _by_head(C.astype(f32), H)

    def token(S, inputs):
        x_t, dt_t, B_t, C_t = inputs
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * B_t)[:, :, None] * x_t[:, None, :])
        return S, jnp.einsum("hnp,hn->hp", S, C_t, precision=_HIGHEST)

    S0 = jnp.zeros((H, B.shape[-1], x.shape[-1]), f32)
    return jax.lax.scan(token, S0, (x, dt, B, C))[1]


def _segment_sums(a):
    """``seg[..., r, i] = sum_{i<k<=r} a[..., k]`` for ``i <= r`` and
    ``-inf`` above the diagonal: ``a`` [..., C] -> [..., C, C]."""
    C = a.shape[-1]
    below = jnp.tril(jnp.ones((C, C), bool), -1)
    sums = jnp.cumsum(jnp.where(below, a[..., :, None], 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((C, C), bool)), sums, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int = 128):
    """``ssd_recurrent`` in chunks of ``chunk`` tokens: the same ``y``
    [L, H, P], in the dtype of ``x``.  A length that ``chunk`` does not
    divide is padded with tokens of ``dt`` 0, which store nothing and decay
    nothing."""
    L, H, P = x.shape
    G, N = B.shape[-2:]
    f32, dtype = jnp.float32, x.dtype
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(t):  # [L, ...] -> [n, chunk, ...]
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(n, chunk, *t.shape[1:])

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    dt = chunks(dt.astype(f32))                                  # [n, c, H]
    xc, Bc, Cc = chunks(x), chunks(B), chunks(C)
    a = jnp.moveaxis(dt * A.astype(f32), 1, 2)                   # [n, H, c]
    seg = _segment_sums(a)                                       # [n, H, c, c]
    # inside a chunk: (C_r . B_i) exp(seg(r, i)) applied to dt_i x_i
    cb = dot("nrgk,nigk->ngri", Cc, Bc)                          # [n, G, c, c]
    cb = jnp.repeat(cb, H // G, axis=1)                          # [n, H, c, c]
    u = xc.astype(f32) * dt[..., None]                           # [n, c, H, P]
    y = dot("nhri,nihp->nrhp", cb * jnp.exp(seg), u)
    # what each chunk writes into the state, and what it reads of S_0
    to_end = jnp.exp(seg[:, :, -1, :])                           # [n, H, c]
    from_start = jnp.exp(jnp.cumsum(a, axis=-1))                 # [n, H, c]
    Bh = _by_head(Bc, H)                                         # [n, c, H, N]
    written = dot("nihk,nihp->nhkp", Bh * jnp.moveaxis(to_end, 1, 2)[..., None],
                  u)                                             # [n, H, N, P]
    kept = from_start[:, :, -1, None, None]                      # [n, H, 1, 1]

    def one_chunk(S, inputs):
        written, kept = inputs
        return kept * S + written, S

    _, entered = jax.lax.scan(one_chunk, jnp.zeros((H, N, P), f32),
                              (written, kept))                   # [n, H, N, P]
    Ch = _by_head(Cc, H) * jnp.moveaxis(from_start, 1, 2)[..., None]
    y = y + dot("nrhk,nhkp->nrhp", Ch, entered)
    return y.reshape(n * chunk, H, P)[:L].astype(dtype)
