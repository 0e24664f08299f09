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
products a chunk.  JAX differentiates the definition and this lax form
(``gated_delta_rule_lax``).

**The kernels.**  Where the shape tiles (``kernels_tile``: heads of whole
128-lane tiles, chunks of whole 16-row tiles) ``gated_delta_rule`` runs the
same chunked computation in four Pallas kernels (``gated_delta_rule_kernels``),
so that a chunk's blocks and the carried state stay in VMEM: one over (chunk,
head) pairs for what does not depend on ``S0`` (cumulative sums, both pair
sums, the decayed ``q`` and ``k``, the solve's right-hand sides), one over the
chunks in order with ``S`` in scratch from the first chunk to the last, and
each one's backward, written by hand, as a kernel of the same shape (the
second keeps each chunk's entry state, as ``lax.scan`` stacks it; the first
keeps nothing but its operands).  Between the two stands the triangular solve,
the one lax op left (Mosaic has none), differentiated by JAX.  The shape alone
chooses: every shape the kernels do not tile runs the lax form, the middle
term between the definition and the kernels.  Off the TPU the kernels run in
Pallas's interpreter.

**No ``exp(-G)``.**  A channel may decay by ``exp(-10)`` a token, so over a
chunk ``exp(-G)`` leaves float32 (``exp(88)``) where ``exp(G_r - G_i)`` for
``i <= r`` never exceeds 1.  The pair sums are therefore taken in sub-blocks
of ``SUB`` rows: a pair inside one sub-block uses the difference itself (a
[SUB, SUB, d] block, summed over channels); a pair across sub-blocks is split
at the row's sub-block start ``m``, ``exp(G_r - G_m) exp(G_m - G_i)`` with
both exponents ``<= 0``, and is a matmul.  The kernels' sub-block is a float32
tile of ``TILE`` = 8 rows, and they split a pair across sub-blocks in levels:
inside a block of 16, 32, ... rows at the block's middle, ``exp(G_r - G_m)
exp(G_m - G_i)`` with ``r`` in the second half and ``i`` in the first, one
matmul a level whatever the number of sub-blocks.  Decays, cumulative sums, the
pair sums (their matmuls at float32 precision) and the triangular solve are
float32 whatever the inputs' dtype; the products with the state take ``v``'s
dtype as operands and add in float32, in every form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import _NT, _TN, _dot

SUB = 16  # rows of a sub-block: its pair sums are taken over explicit differences
TILE = 8  # the same in the kernels: one float32 tile of 8 sublanes
STEP_CHUNKS = 4  # chunks of one head a grid step of the pair sums' kernels
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
    tokens that store nothing and decay nothing.  Where the shape tiles
    (``kernels_tile``) the chunks run in the Pallas kernels, elsewhere as lax
    ops."""
    if chunk > SUB and chunk % SUB:
        raise ValueError(f"chunk {chunk} is no multiple of {SUB}")
    if kernels_tile(q.shape[-1], v.shape[-1], chunk):
        return gated_delta_rule_kernels(q, k, v, g, beta, chunk)
    return gated_delta_rule_lax(q, k, v, g, beta, chunk)


def gated_delta_rule_lax(q, k, v, g, beta, chunk: int = 64):
    """``gated_delta_rule`` as lax ops that JAX differentiates: what does not
    depend on the state for every chunk at once, then a ``lax.scan`` over the
    chunks."""
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


# ---------------------------------------------------------------------------
# The kernels.  A chunk of one head is ``C // TILE`` tiles of [TILE, d]: every
# [C, d] array below is also held as the list of its tiles, and a sum over a
# range of rows is put together from sums over tiles.

_NN = (((1,), (0,)), ((), ()))  # a @ b; ``_NT`` a @ b.T, ``_TN`` a.T @ b


def kernels_tile(dk: int, dv: int, chunk: int) -> bool:
    """Whether the kernels take the shape: heads of whole 128-lane tiles and
    chunks of whole 16-row tiles (what a bf16 block of ``v`` needs)."""
    return dk % 128 == 0 and dv % 128 == 0 and chunk % SUB == 0


def _exact(a, b, dims=_NN):
    """A product of float32 operands at float32 precision, whatever the
    config says (``_dot``, the state products': 16-bit operands in one pass,
    float32 ones by the config)."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _tiles(x):
    return [x[b * TILE:(b + 1) * TILE] for b in range(x.shape[0] // TILE)]


def _rows(tiles):
    return jnp.concatenate(tiles, axis=0)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _segment_sums(g):
    """The sums of ``g`` [C, d] over ranges of rows that the pair sums need,
    as lists of tiles: (``local``: over the rows of the row's tile up to it;
    ``levels``: for blocks of s = 1, 2, 4, ... tiles, (s, the sums from the
    block's first row to the row, the sums from the row, exclusive, to the
    block's last); ``G``: from the chunk's first row; ``rest``: from the row,
    exclusive, to the chunk's last; ``last``: over the whole chunk, every row
    of the tile the same).  A tile's own sums are one product with a matrix of
    ones (exact: float32 precision); a longer range adds whole blocks' totals
    to them, so no difference of two long sums is taken."""
    C = g.shape[0]
    nb = C // TILE
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    ones = jnp.where((row // TILE == col // TILE) & (col <= row), 1.0, 0.0)
    loc = _tiles(_exact(ones, g))
    tot = [jnp.broadcast_to(t[TILE - 1:], t.shape) for t in loc]
    rev = [t - l for t, l in zip(tot, loc)]
    local, levels, s = loc, [], 1
    while s < nb:
        levels.append((s, loc, rev))
        block = [b // s for b in range(nb)]
        loc = [loc[b] + tot[block[b] - 1] if block[b] % 2 else loc[b]
               for b in range(nb)]
        rev = [rev[b] + tot[block[b] + 1]
               if block[b] % 2 == 0 and block[b] + 1 < len(tot) else rev[b]
               for b in range(nb)]
        tot = [tot[j] + tot[j + 1] if j + 1 < len(tot) else tot[j]
               for j in range(0, len(tot), 2)]
        s *= 2
    return local, levels, loc, rev, tot[0]


def _inside(local, i):
    """``exp(G_r - G_i)`` for the rows ``r >= i`` of one tile, 0 above: the
    difference itself, masked before the exponential."""
    return jnp.exp(jnp.where(_iota(local.shape, 0) >= i,
                             local - local[i:i + 1], -jnp.inf))


def _level_decays(level, nb):
    """Across the two halves of a block of 2 s tiles, ``exp(G_r - G_i) =
    exp(G_r - G_m) exp(G_m - G_i)`` with ``m`` the first half's last row and
    both exponents ``<= 0``: (the first factor for the tiles of a second
    half, the second for those of a first half that has a second; None for
    the other tiles)."""
    s, loc, rev = level
    ex = [jnp.exp(loc[b]) if (b // s) % 2 else None for b in range(nb)]
    ey = [jnp.exp(rev[b]) if (b // s) % 2 == 0 and (b // s + 1) * s < nb
          else None for b in range(nb)]
    return ex, ey


def _scaled(tiles, factors):
    """``tiles[b] * factors[b]`` as rows, zeros where the factor is None."""
    return _rows([jnp.zeros_like(t) if f is None else t * f
                  for t, f in zip(tiles, factors)])


def _second_halves(ex, *operands):
    """The rows a level's product takes: of each operand in turn, the tiles
    of second halves (where ``ex`` holds a factor) times their factors."""
    return _rows([t[b] * f for t in operands
                  for b, f in enumerate(ex) if f is not None])


def _first_half_lanes(C, s, b):
    """[TILE, C]: the columns of the first half of tile ``b``'s block of 2 s
    tiles: the pairs a level's product holds for a row of tile ``b``."""
    size = 2 * s * TILE
    return _iota((TILE, C), 1) // size == b * TILE // size


def _pairs_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, a_ref, p_ref,
                      rhs_ref, qin_ref, kout_ref, kept_ref, *, chunk):
    """``STEP_CHUNKS`` chunks of one head, one after another: ``A`` (strictly
    lower), ``P`` (lower), the solve's right-hand sides ``[beta v, beta k
    exp(G)]``, ``q exp(G)``, ``k exp(G_C - G)`` and ``exp(G_C)``."""
    C, dv = chunk, v_ref.shape[1]

    def one_chunk(j, carry):
        first = pl.multiple_of(j * C, C)
        whole = pl.ds(first, C)
        q, k, beta = q_ref[whole, :], k_ref[whole, :], beta_ref[whole, :]
        local, levels, G, rest, last = _segment_sums(g_ref[whole, :])
        qt, kt, xt = _tiles(q), _tiles(k), _tiles(beta * k)
        bv = _tiles(beta * v_ref[whole, :].astype(jnp.float32))
        nb = len(kt)
        # pairs across tiles: a product a level, the second halves' rows of
        # beta k over those of q, against every first half's rows of k
        zero = jnp.zeros((TILE, C), jnp.float32)
        across_a, across_p = [zero] * nb, [zero] * nb
        for level in levels:
            ex, ey = _level_decays(level, nb)
            m = _tiles(_exact(_second_halves(ex, xt, qt), _scaled(kt, ey),
                              _NT))
            second = [b for b in range(nb) if ex[b] is not None]
            for at, b in enumerate(second):
                keep = _first_half_lanes(C, level[0], b)
                across_a[b] = across_a[b] + jnp.where(keep, m[at], 0.0)
                across_p[b] = across_p[b] + jnp.where(
                    keep, m[len(second) + at], 0.0)
        row, lane = _iota((TILE, 1), 0), _iota((TILE, C), 1)
        for b in range(nb):
            tile = slice(b * TILE, (b + 1) * TILE)  # of the chunk's rows
            rows = pl.ds(first + b * TILE, TILE)  # the same of the step's
            blk_a = blk_p = jnp.zeros((TILE, C), jnp.float32)
            for i in range(TILE):  # pairs inside the tile: column i, all rows
                ye = _inside(local[b], i) * kt[b][i:i + 1]
                hit = lane == b * TILE + i
                blk_a = jnp.where(
                    hit, (xt[b] * ye).sum(axis=1, keepdims=True), blk_a)
                blk_p = jnp.where(
                    hit, (qt[b] * ye).sum(axis=1, keepdims=True), blk_p)
            a_ref[j, tile, :] = across_a[b] + jnp.where(
                lane < b * TILE + row, blk_a, 0.0)
            p_ref[j, tile, :] = across_p[b] + blk_p
            decayed = jnp.exp(G[b])
            qin_ref[rows, :] = qt[b] * decayed
            rhs_ref[j, tile, :dv] = bv[b]
            rhs_ref[j, tile, dv:] = xt[b] * decayed
            kout_ref[rows, :] = kt[b] * jnp.exp(rest[b])
        kept_ref[j] = jnp.exp(last[:1])
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0], one_chunk, 0)


def _pairs_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, da_ref, dp_ref,
                      drhs_ref, dqin_ref, dkout_ref, dkept_ref, dq_ref,
                      dk_ref, dv_ref, dg_ref, dbeta_ref, *, chunk):
    """The backward of ``_pairs_fwd_kernel``: the same blocks walked once
    more, nothing kept from the forward but its operands.  With ``e_ric =
    exp(G_rc - G_ic)`` and ``M_ri = sum_c x_rc y_ic e_ric``: ``dx_rc = sum_i
    dM_ri y_ic e_ric``, ``dy_ic = sum_r dM_ri x_rc e_ric``, and ``dG`` takes
    ``+ dM_ri x_rc y_ic e_ric`` at row r and ``-`` the same at row i; ``dg``
    is the reverse cumulative sum of ``dG``.  ``x`` is ``beta k`` for ``A``
    and ``q`` for ``P``; ``y`` is ``k`` for both."""
    f32 = jnp.float32
    C, dv = chunk, v_ref.shape[1]

    def one_chunk(j, carry):
        first = pl.multiple_of(j * C, C)
        whole = pl.ds(first, C)
        q, k, beta = q_ref[whole, :], k_ref[whole, :], beta_ref[whole, :]
        local, levels, G, rest, last = _segment_sums(g_ref[whole, :])
        qt, kt, xt, bt = _tiles(q), _tiles(k), _tiles(beta * k), _tiles(beta)
        nb = len(kt)
        dA, dP = da_ref[j], dp_ref[j]
        zero = jnp.zeros_like(kt[0])
        dxa, dxq, dy, dG = ([zero] * nb for _ in range(4))
        for level in levels:
            ex, ey = _level_decays(level, nb)
            second = [b for b in range(nb) if ex[b] is not None]
            dM = _rows([jnp.where(_first_half_lanes(C, level[0], b),
                                  d[b * TILE:(b + 1) * TILE], 0.0)
                        for d in (dA, dP) for b in second])
            d_rows = _tiles(_exact(dM, _scaled(kt, ey)))
            d_cols = _tiles(_exact(dM, _second_halves(ex, xt, qt), _TN))
            for at, b in enumerate(second):
                ta, tq = d_rows[at] * ex[b], d_rows[len(second) + at] * ex[b]
                dxa[b], dxq[b] = dxa[b] + ta, dxq[b] + tq
                dG[b] = dG[b] + ta * xt[b] + tq * qt[b]
            for b in range(nb):
                if ey[b] is not None:
                    w = d_cols[b] * ey[b]
                    dy[b], dG[b] = dy[b] + w, dG[b] - w * kt[b]
        row = _iota((TILE, 1), 0)
        d_bv, d_rk = drhs_ref[j, :, :dv], _tiles(drhs_ref[j, :, dv:])
        d_qin, d_kout = _tiles(dqin_ref[whole, :]), _tiles(dkout_ref[whole, :])
        out_sum = jnp.zeros((1, q.shape[1]), f32)
        for b in range(nb):
            at = slice(b * TILE, (b + 1) * TILE)
            for i in range(TILE):
                e = _inside(local[b], i)
                y_i = kt[b][i:i + 1]
                column = slice(b * TILE + i, b * TILE + i + 1)
                da = jnp.where(row > i, dA[at, column], 0.0)       # [TILE, 1]
                dq = dP[at, column]
                dxa[b] = dxa[b] + da * (e * y_i)
                dxq[b] = dxq[b] + dq * (e * y_i)
                t = (da * xt[b] + dq * qt[b]) * e
                col = jnp.where(row == i, t.sum(axis=0, keepdims=True), 0.0)
                dy[b] = dy[b] + col
                dG[b] = dG[b] + (t - col) * y_i
            decayed, out = jnp.exp(G[b]), jnp.exp(rest[b])
            moved = d_kout[b] * kt[b] * out
            out_sum = out_sum + moved.sum(axis=0, keepdims=True)
            dG[b] = (dG[b] + (d_qin[b] * qt[b] + d_rk[b] * xt[b]) * decayed
                     - moved)
            dxa[b] = dxa[b] + d_rk[b] * decayed
            dxq[b] = dxq[b] + d_qin[b] * decayed
            dy[b] = bt[b] * dxa[b] + dy[b] + d_kout[b] * out
        # the chunk's last row also decays what leaves the chunk
        dG[-1] = dG[-1] + jnp.where(
            row == TILE - 1, out_sum + dkept_ref[j] * jnp.exp(last[:1]), 0.0)
        later = jnp.where(_iota((C, C), 1) >= _iota((C, C), 0), 1.0, 0.0)
        dg_ref[whole, :] = _exact(later, _rows(dG))
        dq_ref[whole, :] = _rows(dxq)
        dk_ref[whole, :] = _rows(dy)
        dv_ref[whole, :] = (beta * d_bv).astype(dv_ref.dtype)
        dbeta_ref[whole, :] = (
            (k * _rows(dxa)).sum(axis=1, keepdims=True)
            + (d_bv * v_ref[whole, :].astype(f32)).sum(axis=1, keepdims=True))
        return carry

    jax.lax.fori_loop(0, da_ref.shape[0], one_chunk, 0)


def _scan_fwd_kernel(solved_ref, p_ref, qin_ref, kout_ref, kept_ref, o_ref,
                     entry_ref, state, *, heads):
    """One chunk of every head, the chunks in order: the state, kept
    transposed ([d_v, d_k]: ``exp(G_C)`` then scales its lanes), stays in
    ``state`` from the first chunk to the last.  Writes ``o`` and the state
    the chunk was entered with, which the backward reads."""
    dv, dk = state.shape[1:]
    dtype = o_ref.dtype

    @pl.when(pl.program_id(0) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        of_k, of_v = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        S = state[h]
        entry_ref[h] = S
        Sb = S.astype(dtype)
        N = solved_ref[h, :, :dv] - _dot(
            solved_ref[h, :, dv:].astype(dtype), Sb, _NT)
        Nb = N.astype(dtype)
        o = (_dot(qin_ref[:, of_k].astype(dtype), Sb, _NT)
             + _dot(p_ref[h].astype(dtype), Nb))
        o_ref[:, of_v] = o.astype(dtype)
        state[h] = S * kept_ref[:, of_k] + _dot(
            Nb, kout_ref[:, of_k].astype(dtype), _TN)


def _scan_bwd_kernel(do_ref, solved_ref, p_ref, qin_ref, kout_ref, kept_ref,
                     entry_ref, dsolved_ref, dp_ref, dqin_ref, dkout_ref,
                     dkept_ref, dstate, *, heads):
    """The chunks in reverse, the state's cotangent (transposed, as the
    state) in ``dstate``: with ``S`` the state the chunk was entered with and
    ``dS'`` what the chunks after it gave, ``dN = P^T do + k_out dS'``, ``dP
    = tril(do N^T)``, ``dq_in = do S^T``, ``dk_out = N dS'^T``, ``dkept =
    rowsum(S * dS')``, ``dU = dN``, ``dW = -dN S^T``, ``dS = q_in^T do +
    kept * dS' - W^T dN``.  ``N`` is computed again from ``S``."""
    dv, dk = dstate.shape[1:]
    C = do_ref.shape[0]
    dtype = do_ref.dtype

    @pl.when(pl.program_id(0) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    lower = _iota((C, C), 0) >= _iota((C, C), 1)
    for h in range(heads):
        of_k, of_v = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        S, dS = entry_ref[h], dstate[h]
        Sb, dSb = S.astype(dtype), dS.astype(dtype)
        Wb = solved_ref[h, :, dv:].astype(dtype)
        Nb = (solved_ref[h, :, :dv] - _dot(Wb, Sb, _NT)).astype(dtype)
        do = do_ref[:, of_v]
        dN = (_dot(p_ref[h].astype(dtype), do, _TN)
              + _dot(kout_ref[:, of_k].astype(dtype), dSb, _NT))
        dNb = dN.astype(dtype)
        dp_ref[h] = jnp.where(lower, _dot(do, Nb, _NT), 0.0)
        dqin_ref[:, of_k] = _dot(do, Sb)
        dkout_ref[:, of_k] = _dot(Nb, dSb)
        dkept_ref[:, of_k] = (S * dS).sum(axis=0, keepdims=True)
        dsolved_ref[h, :, :dv] = dN
        dsolved_ref[h, :, dv:] = -_dot(dNb, Sb)
        dstate[h] = (_dot(do, qin_ref[:, of_k].astype(dtype), _TN)
                     + dS * kept_ref[:, of_k] - _dot(dNb, Wb, _TN))


def _interpret() -> bool:
    """Off the TPU the kernels run in Pallas's interpreter (the
    ``ops/conv_mxu.py`` precedent)."""
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _pairs_call(n, H, C, dk, dv, dtype, interpret, backward):
    """The ``pallas_call`` of ``_pairs_fwd_kernel`` or its backward at a
    shape, built once: every layer and both directions of a model call the
    same object, which traces its kernel's body once (PERF.md, PR 36).  A
    grid step is ``STEP_CHUNKS`` chunks of one head (or as many as divide the
    ``n`` there are); the steps are independent."""
    f32 = jnp.float32
    J = next(j for j in range(STEP_CHUNKS, 0, -1) if n % j == 0)
    rows_k = pl.BlockSpec((J * C, dk), lambda c, h: (c, h))
    rows_v = pl.BlockSpec((J * C, dv), lambda c, h: (c, h))
    column = pl.BlockSpec((None, J * C, 1), lambda c, h: (h, c, 0))
    square = pl.BlockSpec((J, None, C, C), lambda c, h: (c, h, 0, 0))
    sides = pl.BlockSpec((J, None, C, dv + dk), lambda c, h: (c, h, 0, 0))
    kept = pl.BlockSpec((J, 1, dk), lambda c, h: (c, 0, h))
    shape = jax.ShapeDtypeStruct
    L = n * C
    inputs = [rows_k, rows_k, rows_v, rows_k, column]
    terms = [square, square, sides, rows_k, rows_k, kept]
    if backward:
        kernel, in_specs, out_specs = _pairs_bwd_kernel, inputs + terms, inputs
        out_shape = [shape((L, H * dk), f32), shape((L, H * dk), f32),
                     shape((L, H * dv), dtype), shape((L, H * dk), f32),
                     shape((H, L, 1), f32)]
    else:
        kernel, in_specs, out_specs = _pairs_fwd_kernel, inputs, terms
        out_shape = [shape((n, H, C, C), f32), shape((n, H, C, C), f32),
                     shape((n, H, C, dv + dk), f32), shape((L, H * dk), f32),
                     shape((L, H * dk), f32), shape((n, 1, H * dk), f32)]
    return pl.pallas_call(
        functools.partial(kernel, chunk=C), grid=(n // J, H),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_pairs_bwd" if backward else "kda_pairs_fwd")


@functools.lru_cache(maxsize=None)
def _scan_call(n, H, C, dk, dv, dtype, interpret, backward):
    """The ``pallas_call`` of ``_scan_fwd_kernel`` or its backward at a shape,
    built once.  A grid step is one chunk of every head; the steps run in
    order (the backward's from the last chunk to the first) on one core."""
    f32 = jnp.float32

    def at(c):  # the chunk of grid step c
        return n - 1 - c if backward else c

    rows_k = pl.BlockSpec((C, H * dk), lambda c: (at(c), 0))
    rows_v = pl.BlockSpec((C, H * dv), lambda c: (at(c), 0))
    solved = pl.BlockSpec((None, H, C, dv + dk), lambda c: (at(c), 0, 0, 0))
    square = pl.BlockSpec((None, H, C, C), lambda c: (at(c), 0, 0, 0))
    kept = pl.BlockSpec((None, 1, H * dk), lambda c: (at(c), 0, 0))
    entry = pl.BlockSpec((None, H, dv, dk), lambda c: (at(c), 0, 0, 0))
    shape = jax.ShapeDtypeStruct
    L = n * C
    terms = [solved, square, rows_k, rows_k, kept]
    term_shapes = [shape((n, H, C, dv + dk), f32), shape((n, H, C, C), f32),
                   shape((L, H * dk), f32), shape((L, H * dk), f32),
                   shape((n, 1, H * dk), f32)]
    if backward:
        kernel, in_specs = _scan_bwd_kernel, [rows_v] + terms + [entry]
        out_specs, out_shape = terms, term_shapes
    else:
        kernel, in_specs = _scan_fwd_kernel, terms
        out_specs = [rows_v, entry]
        out_shape = [shape((L, H * dv), dtype), shape((n, H, dv, dk), f32)]
    return pl.pallas_call(
        functools.partial(kernel, heads=H), grid=(n,), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H, dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kda_scan_bwd" if backward else "kda_scan_fwd")


def _shape_of(q, v, beta, chunk):
    """(chunks, heads, chunk, d_k, d_v, v's dtype) of the flat operands."""
    H, L = beta.shape[:2]
    return (L // chunk, H, chunk, q.shape[1] // H, v.shape[1] // H,
            jnp.dtype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunk_terms(q, k, v, g, beta, chunk):
    """What a chunk computes before it sees a state.  ``q``, ``k``, ``g`` [L,
    H d_k] float32, ``v`` [L, H d_v], ``beta`` [H, L, 1] float32, ``L`` whole
    chunks; returns ``A``, ``P`` [n, H, C, C], the solve's right-hand sides
    [n, H, C, d_v + d_k], ``q_in``, ``k_out`` [L, H d_k] and ``kept`` [n, 1,
    H d_k], all float32."""
    return _pairs_call(*_shape_of(q, v, beta, chunk), _interpret(), False)(
        q, k, v, g, beta)


def _chunk_terms_fwd(q, k, v, g, beta, chunk):
    return _chunk_terms(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _chunk_terms_bwd(chunk, operands, cotangents):
    q, _, v, _, beta = operands
    return tuple(_pairs_call(*_shape_of(q, v, beta, chunk), _interpret(),
                             True)(*operands, *cotangents))


_chunk_terms.defvjp(_chunk_terms_fwd, _chunk_terms_bwd)


def _scan_shape(solved, q_in, dtype):
    n, H, C, both = solved.shape
    dk = q_in.shape[1] // H
    return n, H, C, dk, both - dk, jnp.dtype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _carried(solved, P, q_in, k_out, kept, dtype):
    """The recurrence over the chunks: ``o`` [L, H d_v] in ``dtype``."""
    return _carried_fwd(solved, P, q_in, k_out, kept, dtype)[0]


def _carried_fwd(solved, P, q_in, k_out, kept, dtype):
    terms = (solved, P, q_in, k_out, kept)
    o, entries = _scan_call(*_scan_shape(solved, q_in, dtype), _interpret(),
                            False)(*terms)
    return o, (*terms, entries)


def _carried_bwd(dtype, residuals, do):
    solved, _, q_in = residuals[:3]
    return tuple(_scan_call(*_scan_shape(solved, q_in, dtype), _interpret(),
                            True)(do, *residuals))


_carried.defvjp(_carried_fwd, _carried_bwd)


def gated_delta_rule_kernels(q, k, v, g, beta, chunk: int = 64):
    """``gated_delta_rule`` where ``kernels_tile``: a chunk's blocks and the
    carried state live in VMEM.  One kernel over (chunk, head) pairs takes
    the cumulative sums and the pair sums; the triangular solve stays the one
    lax op (Mosaic has none), differentiated by JAX; one kernel walks the
    chunks in order with the state in scratch.  Each kernel's backward is a
    kernel written by hand."""
    L, H, dk = q.shape
    if not kernels_tile(dk, v.shape[-1], chunk):
        raise ValueError(f"the kernels do not tile heads of {dk} and "
                         f"{v.shape[-1]} in chunks of {chunk}")
    f32 = jnp.float32
    n = -(-L // chunk)

    def flat(t, dtype):  # [L, H, d] -> [n C, H d]
        t = jnp.pad(t.astype(dtype), ((0, n * chunk - L), (0, 0), (0, 0)))
        return t.reshape(n * chunk, -1)

    beta = jnp.pad(beta.astype(f32), ((0, n * chunk - L), (0, 0))).T[..., None]
    A, P, sides, q_in, k_out, kept = _chunk_terms(
        flat(q, f32), flat(k, f32), flat(v, v.dtype), flat(g, f32), beta,
        chunk)
    # the diagonal is taken as ones and the upper triangle is not read
    solved = jax.lax.linalg.triangular_solve(
        A, sides, left_side=True, lower=True, unit_diagonal=True)
    o = _carried(solved, P, q_in, k_out, kept, v.dtype)
    return o[:L].reshape(L, H, -1)
