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

where ``S_0`` is the state the chunk enters with.  In the lax form
(``ssd_chunked_lax``) everything but ``S_0`` is computed for every chunk at
once; a ``lax.scan`` over the chunks carries the [N, P] state, one multiply-add
a chunk.  JAX differentiates the definition and the lax form.

**The kernels.**  Where the shape tiles (``kernels_tile``: a state of whole
128-lane tiles, a group's ``H P / G`` channels of whole 128-row tiles, heads of
whole 16-row tiles, chunks of whole 128-lane tiles) ``ssd_chunked`` runs the
same chunks in two Pallas kernels (``ssd_kernels``): one walks the chunks in
order with each group's state in VMEM scratch, building a chunk's sums, decay
masks and ``B C^T`` block there, so that no [n, H, C, C] array reaches HBM; its
backward, written by hand, walks them in reverse with the state's cotangent in
scratch.  The custom VJP keeps the inputs and the [n, G, H P / G, N] float32
states the chunks were entered with, nothing else.  A chunk is held with its
tokens along the lanes, so that a head of ``P`` channels (64 in the Nemotron-H
cell, half a lane tile) is a block of rows and every product emits whole lane
tiles; the heads of a group share one product with ``C`` and one with ``B``.
The shape alone chooses: every shape the kernels do not tile runs the lax
form, the middle term between the definition and the kernels.  Off the TPU
the kernels run in Pallas's interpreter.

**No ``exp(-cumsum)``.**  A head may decay by ``exp(-10)`` a token, so the
exponent is always a sum of the decays between two tokens, taken as that
sum (a cumulative sum along the pair's own row, masked before the ``exp``;
in the kernels a product of the masked decays with a matrix of ones, each
decay split into three bf16 parts so that the MXU adds them exactly), never
as the difference of two large cumulative sums nor as a product of
``exp(cumsum)`` and ``exp(-cumsum)``.  Decays, their sums and the decay masks
are float32 whatever the inputs' dtype; the products take ``x``'s dtype as
operands and add in float32.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import _NT, _TN, _dot
from fedml_tpu.ops.linear_attention import _NN, _interpret, _iota

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
    nothing.  Where the shape tiles (``kernels_tile``) the chunks run in the
    Pallas kernels, elsewhere as lax ops."""
    L, H, P = x.shape
    G, N = B.shape[-2:]
    if kernels_tile(N, H * P // G, P, chunk):
        return ssd_kernels(x, dt, A, B, C, chunk)
    return ssd_chunked_lax(x, dt, A, B, C, chunk)


def ssd_chunked_lax(x, dt, A, B, C, chunk: int = 128):
    """``ssd_chunked`` as lax ops that JAX differentiates: what does not
    depend on the state for every chunk at once, then a ``lax.scan`` over
    the chunks."""
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


# ---------------------------------------------------------------------------
# The kernels.  A chunk is held transposed, tokens along the lanes: a group's
# heads are [H P / G, C] (a head the ``P`` rows from ``h P``), its decays
# [H / G, C], the state [H P / G, N].  Every per-head quantity is then a row
# or a block of whole rows, and every product emits whole 128-lane tiles.


def kernels_tile(state: int, width: int, head_dim: int, chunk: int) -> bool:
    """Whether the kernels take the shape: a state of whole 128-lane tiles,
    a group's heads (``H P / G`` channels) of whole 128-row tiles, heads of
    whole 16-row bf16 tiles and chunks of whole 128-lane tiles."""
    return (state % 128 == 0 and width % 128 == 0 and head_dim % 16 == 0
            and chunk % 128 == 0)


def _split(v):
    """``v`` (float32) as three bf16 parts whose sum is ``v``: a product of
    each with a matrix of zeros and ones is exact, so three such products
    add up float32 sums of ``v``'s entries in one MXU pass each."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _sums(v, ones, dims=_NN):
    """``v @ ones`` in float32 for ``ones`` of zeros and ones (bf16)."""
    return sum(_dot(part, ones, dims) for part in _split(v))


def _ones(C, relation):
    """[C, C] bf16: 1 where ``relation(row, column)``."""
    return jnp.where(relation(_iota((C, C), 0), _iota((C, C), 1)),
                     1.0, 0.0).astype(jnp.bfloat16)


def _decays(a):
    """For the heads' log decays ``a`` [h, C] of one chunk: (``exp`` of the
    sums from the chunk's first token to each token, inclusive: what a token
    reads of the entry state; ``exp`` of the sums from each token, exclusive,
    to the chunk's last: what a token's write keeps at the chunk's end;
    ``exp`` of the whole chunk's sum [h, 1]: what the state keeps)."""
    C = a.shape[1]
    whole = jnp.sum(a, axis=1, keepdims=True)
    return (jnp.exp(_sums(a, _ones(C, operator.le))),
            jnp.exp(_sums(a, _ones(C, operator.gt))), jnp.exp(whole))


def _mask(a_row):
    """``M[i, r] = exp(sum_{i<k<=r} a_k)`` for ``i <= r``, 0 below: the
    decay from token ``i`` to token ``r`` of one head, transposed (columns
    are the reading tokens).  Each exponent is the sum itself, a product of
    the decays masked to ``k > i`` with the ones of ``k <= r``."""
    C = a_row.shape[1]
    later, upto = _iota((C, C), 1) > _iota((C, C), 0), _ones(C, operator.le)
    seg = sum(_dot(jnp.where(later, part.astype(jnp.float32), 0.0).astype(
        jnp.bfloat16), upto) for part in _split(a_row))
    return jnp.where(_iota((C, C), 0) <= _iota((C, C), 1), jnp.exp(seg), 0.0)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, entry_ref, state,
                *, heads):
    """One chunk of one group's heads, the chunks in order: the state stays
    in ``state`` from the first chunk to the last.  Writes ``y`` and the
    state the chunk was entered with, which the backward reads."""
    f32, dtype = jnp.float32, x_ref.dtype
    C, P = x_ref.shape[0], x_ref.shape[1] // heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    dt, a = dt_ref[...], a_ref[...]
    Bc, Cc = b_ref[...], c_ref[...]
    reads, keeps, kept = _decays(a)
    cb = _dot(Bc, Cc, _NT)                                  # [i, r]: B_i . C_r
    xt = x_ref[...].astype(f32).T                           # [h P, C]
    S = state[...]
    entry_ref[...] = S
    from_state = _dot(S.astype(dtype), Cc, _NT)             # [h P, C]
    ys = []
    for h in range(heads):
        rows = slice(h * P, (h + 1) * P)
        u = xt[rows] * dt[h:h + 1]
        inside = (cb * _mask(a[h:h + 1])).astype(dtype)
        ys.append(_dot(u.astype(dtype), inside)
                  + from_state[rows] * reads[h:h + 1])
        state[rows, :] = S[rows] * kept[h:h + 1] + _dot(
            (u * keeps[h:h + 1]).astype(dtype), Bc)
    y_ref[...] = jnp.concatenate(ys, axis=0).T.astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, entry_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dstate, staged,
                *, heads):
    """The chunks in reverse, the cotangent of the state a chunk leaves with
    in ``dstate``: with ``S`` the state the chunk was entered with (the
    forward's), ``dS'`` what the chunks after it gave, ``u = dt x``, ``W =
    (B C^T) o M`` and ``v = u`` times the decay to the chunk's end,

        du  = W dy  +  (decay to the end) (B dS'^T),   dW = u dy^T
        dS  = kept dS' + C^T (reads o dy),             dkept = <S, dS'>
        dB  = (dW o M) C + v dS',    dC = (dW o M)^T B + (reads o dy)^T S

    and the log decays' cotangent from ``dW o B C^T o M`` (through every
    pair's sum), the reads', the keeps' and ``dkept``.  Nothing of the
    forward is kept but ``S``."""
    f32, dtype = jnp.float32, x_ref.dtype
    C, P = x_ref.shape[0], x_ref.shape[1] // heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    dt, a = dt_ref[...], a_ref[...]
    Bc, Cc = b_ref[...], c_ref[...]
    reads, keeps, kept = _decays(a)
    cb = _dot(Bc, Cc, _NT)
    xt = x_ref[...].astype(f32).T
    dyt = dy_ref[...].astype(f32).T
    S, dS = entry_ref[...], dstate[...]
    Sb, dSb = S.astype(dtype), dS.astype(dtype)
    from_state = _dot(Sb, Cc, _NT)                          # [h P, C]
    d_written = _dot(dSb, Bc, _NT)                          # [h P, C]
    earlier = _iota((C, C), 0) < _iota((C, C), 1)           # i < k
    to_later = _ones(C, operator.ge)                        # [r, k]: r >= k
    d_cb = jnp.zeros((C, C), f32)
    dxs, dys, vs = [], [], []
    for h in range(heads):
        rows = slice(h * P, (h + 1) * P)
        u = xt[rows] * dt[h:h + 1]
        ub, dy = u.astype(dtype), dyt[rows]
        dyb = dy.astype(dtype)
        M = _mask(a[h:h + 1])
        dW = _dot(ub, dyb, _TN)                             # [i, r]
        d_cb = d_cb + dW * M
        d_seg = dW * cb * M
        dv = d_written[rows]
        du = (_dot(dyb, (cb * M).astype(dtype), _NT)
              + dv * keeps[h:h + 1])
        dys.append((dy * reads[h:h + 1]).astype(dtype))
        vs.append((u * keeps[h:h + 1]).astype(dtype))
        dxs.append(du * dt[h:h + 1])
        ddt_ref[h:h + 1, :] = jnp.sum(du * xt[rows], axis=0, keepdims=True)
        # the log decays: every pair (i, r) adds its cotangent to the k of
        # i < k <= r; the reads' and keeps' go through the staged rows below
        d_kept = jnp.sum(jnp.sum(dS[rows] * S[rows], axis=1, keepdims=True),
                         axis=0, keepdims=True) * kept[h:h + 1]
        da_ref[h:h + 1, :] = d_kept + jnp.sum(
            jnp.where(earlier, _sums(d_seg, to_later), 0.0), axis=0,
            keepdims=True)
        staged[h:h + 1, :] = reads[h:h + 1] * jnp.sum(
            dy * from_state[rows], axis=0, keepdims=True)
        staged[heads + h:heads + h + 1, :] = keeps[h:h + 1] * jnp.sum(
            dv * u, axis=0, keepdims=True)
        dstate[rows, :] = dS[rows] * kept[h:h + 1]
    # a read of token r sums the decays of k <= r, a keep of token i those
    # of k > i
    da_ref[...] = (da_ref[...] + _sums(staged[:heads], to_later)
                   + _sums(staged[heads:], _ones(C, operator.lt)))
    dyr, v = jnp.concatenate(dys, axis=0), jnp.concatenate(vs, axis=0)
    dstate[...] = dstate[...] + _dot(dyr, Cc)
    d_cbb = d_cb.astype(dtype)
    db_ref[...] = _dot(d_cbb, Cc) + _dot(v, dSb, _TN)
    dc_ref[...] = _dot(d_cbb, Bc, _TN) + _dot(dyr, Sb, _TN)
    dx_ref[...] = jnp.concatenate(dxs, axis=0).T.astype(dx_ref.dtype)


@functools.lru_cache(maxsize=None)
def _scan_call(n, G, heads, P, N, C, dtype, interpret, backward):
    """The ``pallas_call`` of ``_fwd_kernel`` or its backward at a shape,
    built once: every layer and both directions of a model call the same
    object, which traces its kernel's body once.  A grid
    step is one chunk of one group's heads; the groups are independent, the
    chunks run in order (the backward's from the last to the first)."""
    f32, D = jnp.float32, heads * P

    def at(c):  # the chunk of grid step c
        return n - 1 - c if backward else c

    rows = pl.BlockSpec((C, D), lambda g, c: (at(c), g))
    decays = pl.BlockSpec((None, heads, C), lambda g, c: (g, 0, at(c)))
    keys = pl.BlockSpec((C, N), lambda g, c: (at(c), g))
    entry = pl.BlockSpec((None, None, D, N), lambda g, c: (at(c), g, 0, 0))
    shape = jax.ShapeDtypeStruct
    L = n * C
    inputs = [rows, decays, decays, keys, keys]
    if backward:
        kernel = _bwd_kernel
        in_specs, out_specs = inputs + [entry, rows], inputs
        out_shape = [shape((L, G * D), dtype), shape((G, heads, L), f32),
                     shape((G, heads, L), f32), shape((L, G * N), f32),
                     shape((L, G * N), f32)]
        scratch = [pltpu.VMEM((D, N), f32), pltpu.VMEM((2 * heads, C), f32)]
    else:
        kernel, in_specs, out_specs = _fwd_kernel, inputs, [rows, entry]
        out_shape = [shape((L, G * D), dtype), shape((n, G, D, N), f32)]
        scratch = [pltpu.VMEM((D, N), f32)]
    return pl.pallas_call(
        functools.partial(kernel, heads=heads), grid=(G, n),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ssd_bwd" if backward else "ssd_fwd")


def _shape_of(x, dt, B, chunk):
    """(chunks, groups, heads a group, P, N, chunk, x's dtype)."""
    G, heads, L = dt.shape
    return (L // chunk, G, heads, x.shape[1] // (G * heads),
            B.shape[1] // G, chunk, jnp.dtype(x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, a, B, C, chunk):
    """The recurrence over whole chunks.  ``x`` [L, H P], ``dt`` and ``a``
    (``dt A``) [G, H / G, L] float32, ``B`` and ``C`` [L, G N] in ``x``'s
    dtype; returns ``y`` [L, H P] in ``x``'s dtype."""
    return _scan_fwd(x, dt, a, B, C, chunk)[0]


def _scan_fwd(x, dt, a, B, C, chunk):
    y, entries = _scan_call(*_shape_of(x, dt, B, chunk), _interpret(),
                            False)(x, dt, a, B, C)
    return y, (x, dt, a, B, C, entries)


def _scan_bwd(chunk, residuals, dy):
    x, dt, _, B = residuals[:4]
    dx, ddt, da, dB, dC = _scan_call(*_shape_of(x, dt, B, chunk),
                                     _interpret(), True)(*residuals, dy)
    return dx, ddt, da, dB.astype(B.dtype), dC.astype(B.dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_kernels(x, dt, A, B, C, chunk: int = 128):
    """``ssd_chunked`` where ``kernels_tile``: one kernel walks the chunks
    in order with the [H P / G, N] state of each group in VMEM, building a
    chunk's decays, masks and ``B C^T`` block there; its backward, written by
    hand, walks them in reverse and keeps nothing of the forward but the
    states the chunks were entered with."""
    L, H, P = x.shape
    G, N = B.shape[-2:]
    if not kernels_tile(N, H * P // G, P, chunk):
        raise ValueError(f"the kernels do not tile a state of {N}, "
                         f"{H * P // G} channels a group, heads of {P} in "
                         f"chunks of {chunk}")
    f32, dtype = jnp.float32, x.dtype
    n = -(-L // chunk)

    def flat(t):  # [L, ...] -> [n C, prod(...)], padded with zeros
        t = jnp.pad(t, ((0, n * chunk - L),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(n * chunk, -1)

    dt = flat(dt.astype(f32)).T.reshape(G, H // G, n * chunk)
    y = _scan(flat(x), dt, dt * A.astype(f32).reshape(G, H // G, 1),
              flat(B.astype(dtype)), flat(C.astype(dtype)), chunk)
    return y[:L].reshape(L, H, P)
