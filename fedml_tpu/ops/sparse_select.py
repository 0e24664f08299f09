"""Keys chosen by a learned indexer: index scores and the exact top-k of a row.

A sparse-attention layer (``models/decoder.py``, kind ``sparse_attention``)
lets a query see only the ``topk`` keys its indexer scores highest.  The
indexer gives a token ``Hi`` index queries ``qI`` [L, Hi, di], one index key
``kI`` [L, di] that all index heads share, and a weight an index head ``w``
[L, Hi]; the score of the pair (t, s) is

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32 sums)

and query ``t`` sees ``S_t``: the ``min(t + 1, topk)`` positions ``s <= t``
with the largest ``I[t, s]``, equal scores to the lower ``s``.  One set a
token, shared by every attention head.

**The definition** is ``select_by_sort``: ``lax.top_k`` of the causally masked
scores.  It sorts every row (67 M values a layer-step at L = 8192), and what
it returns, ``topk`` positions a row, is no form an attention function takes
without a scatter of as many indices.  **What the model runs** is
``select_topk``: the k-th largest score of a row by bisection on the float's
ordered integer image (32 compare-and-count passes over a block of rows), then
``>`` that threshold plus the first equals by a running count.  ``relu`` makes
exact zeros, so equal scores do occur and are counted, not hoped away.  Both
give the same set, ties included (``tests/test_sparse_select.py``).

**Two forms of the one algorithm**, chosen by what the call can see
(``kernel_tiles``; no argument, flag or environment variable).  As lax ops
(``select_in_lax``; exact at every shape) a block of 512 query rows scores all
``L`` keys, masks those above the diagonal and carries them through the 32
counts.  On a TPU, where the length is whole tiles of 512 and the index
operands are bfloat16, ``select_in_kernel`` is one Pallas kernel whose row
block ``i`` scores and counts key tiles ``0 .. i`` alone (136 of the 256 tiles
of a sequence of 8192), keeps their images in VMEM from the product to the
mask, runs no pass at all where the block has at most ``topk`` causal keys,
counts the equals along a row only where some row has more of them than room,
and writes the block's row of the tile table itself.

The choice leaves here as a ``[L, L]`` int8 keep mask (causal: a kept pair
has ``s <= t``) and a ``[L / block, L / block]`` int32 table of the tiles
that hold a kept pair, which is what ``blockwise_attention(keep=)`` and
``flash_attention(keep=, tiles=)`` take.  Nothing here is differentiated: the
choice is discrete, and its inputs are cut from the gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import (
    _NT, VMEM_LIMIT, _dot, _keep, head_group, pick_block,
)

ROWS = 512  # query rows scored and chosen at a time
_HIGHEST = lax.Precision.HIGHEST


def tile_side(length: int) -> int:
    """Side of the table's tiles for a sequence of ``length``: the attention
    kernels' block for it (``pick_block``: 512 where that divides the
    length), or the whole length (one tile) where they take none."""
    return pick_block(length) or length


def _score_rows(qI, kI, w):
    """``I`` of the query rows ``qI`` [r, Hi, di], ``w`` [r, Hi] against every
    index key ``kI`` [L, di]: [r, L] float32, zeros all +0.  The products
    take ``qI``'s dtype as their operands': float32 at full precision, a
    16-bit dtype in one MXU pass; sums, ``relu`` and weights are float32."""
    f32 = jnp.float32
    dots = jnp.einsum(
        "qhd,kd->qhk", qI, kI.astype(qI.dtype), preferred_element_type=f32,
        precision=_HIGHEST if qI.dtype == f32 else None)
    scores = (jax.nn.relu(dots) * w.astype(f32)[:, :, None]).sum(axis=1)
    # a negative weight on a zero makes -0: one zero, so that equal is equal
    return jnp.where(scores == 0, 0.0, scores)


def _in_row_blocks(fn, rows, *operands):
    """``fn`` over blocks of ``rows`` query rows of every operand ([L, ...]),
    the outputs put back together along the rows."""
    L = operands[0].shape[0]
    if L <= rows or L % rows:
        return fn(jnp.arange(L), *operands)
    n = L // rows
    out = lax.map(
        lambda block: fn(*block),
        (jnp.arange(L).reshape(n, rows),
         *(o.reshape(n, rows, *o.shape[1:]) for o in operands)))
    return jax.tree_util.tree_map(
        lambda o: o.reshape(L, *o.shape[2:]), out)


def index_scores(qI, kI, w, rows: int = ROWS):
    """``I`` [L, L] float32 of ``qI`` [L, Hi, di], ``kI`` [L, di], ``w``
    [L, Hi], computed ``rows`` queries at a time; not masked."""
    return _in_row_blocks(lambda _, q, ww: _score_rows(q, kI, ww), rows,
                          qI, w)


def _ordered(x):
    """float32 -> uint32 whose unsigned order is the floats' (no NaN): a set
    sign bit flips every bit, a clear one sets it."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(keys, k: int):
    """[r] the largest value ``v`` with at least ``k`` of a row's ``keys``
    [r, L] (uint32) ``>= v``, built a bit at a time from the top: 0 for a row
    with fewer than ``k`` keys above 0."""
    def bit(i, best):
        trial = best | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= trial[:, None]).sum(axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, best)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:1], jnp.uint32))


def _running_count(flags):
    """Inclusive count along a row of ``flags`` [r, L] (bool) as int32.  In
    128-wide pieces as a product with a triangle of ones where the length
    allows (exact: 0/1 operands, float32 sums), the pieces' totals carried
    over by a short cumulative sum."""
    r, L = flags.shape
    if L % 128:
        return jnp.cumsum(flags, axis=1, dtype=jnp.int32)
    pieces = flags.reshape(r, L // 128, 128).astype(jnp.bfloat16)
    upper = jnp.triu(jnp.ones((128, 128), jnp.bfloat16))
    inside = jnp.einsum("rpi,ij->rpj", pieces, upper,
                        preferred_element_type=jnp.float32)
    before = jnp.cumsum(inside[:, :, -1], axis=1) - inside[:, :, -1]
    return (inside + before[:, :, None]).reshape(r, L).astype(jnp.int32)


def _choose_rows(scores, qpos, topk: int):
    """Keep mask [r, L] (bool) of the rows at positions ``qpos`` [r] with
    ``scores`` [r, L]: the definition's set, by threshold and count."""
    causal = jnp.arange(scores.shape[1])[None, :] <= qpos[:, None]
    # a key under the mask is 0, below every score's image
    keys = jnp.where(causal, _ordered(scores), jnp.uint32(0))
    kth = _kth_largest(keys, topk)[:, None]
    above = keys > kth
    # a row of at most topk causal keys has kth = 0 and all of them above it
    level = (keys == kth) & causal
    room = topk - above.sum(axis=1, dtype=jnp.int32, keepdims=True)
    return above | (level & (_running_count(level) <= room))


def live_tiles(keep, block: int):
    """[L / block, L / block] int32: 1 where the tile holds a kept pair."""
    L = keep.shape[0]
    n = L // block
    return (keep.reshape(n, block, n, block) != 0).any(axis=(1, 3)).astype(
        jnp.int32)


def select_in_lax(qI, kI, w, topk: int, block: int = 0, rows: int = ROWS):
    """``select_topk`` as lax ops, exact at every shape: a block of ``rows``
    queries scores every key, masks those above the diagonal and carries them
    through the 32 counts."""
    keep = _in_row_blocks(
        lambda qpos, q, ww: _choose_rows(_score_rows(q, kI, ww), qpos,
                                         topk).astype(jnp.int8),
        rows, qI, w)
    return keep, live_tiles(keep, block or tile_side(keep.shape[0]))


def select_topk(qI, kI, w, topk: int, block: int = 0, rows: int = ROWS):
    """(keep [L, L] int8, tiles [L / block, L / block] int32) of the choice:
    ``keep[t, s]`` is 1 where ``s`` is in ``S_t``, ``tiles`` as
    ``live_tiles`` (``block`` 0: ``tile_side(L)``).  Scores and choice run
    ``rows`` queries at a time, so no [L, L] float32 reaches HBM: in the
    kernel where the call's shape tiles (``kernel_tiles``), as lax ops
    (``select_in_lax``) everywhere else."""
    qI, kI, w = (lax.stop_gradient(t) for t in (qI, kI, w))
    if kernel_tiles(qI, kI, block, rows):
        return select_in_kernel(qI, kI, w, topk)
    return select_in_lax(qI, kI, w, topk, block, rows)


def tiles_scored(qI, kI, block: int = 0, rows: int = ROWS) -> int:
    """How many ``block`` x ``block`` tiles of index scores ``select_topk``
    computes for these operands: the causal ones, ``n (n + 1) / 2``, in the
    kernel; all ``n x n`` as lax ops, which score the keys above the diagonal
    and then mask them."""
    n = qI.shape[0] // (block or tile_side(qI.shape[0]))
    return n * (n + 1) // 2 if kernel_tiles(qI, kI, block, rows) else n * n


# ---------------------------------------------------------------------------
# The kernel.  A grid step is one block of ``ROWS`` query rows against the
# key tiles up to its own: what lies above the diagonal is neither scored nor
# counted, and a block's scores stay in VMEM from the product to the mask.

# longest sequence whose row block of scores ([ROWS, L] int32) and mask
# ([ROWS, L] int8, two buffers) fit beside the index keys under VMEM_LIMIT
MAX_KERNEL_LENGTH = 16384
# a key under the causal mask: below the image of every score
_MASKED = -2 ** 31


def kernel_tiles(qI, kI, block: int = 0, rows: int = ROWS) -> bool:
    """Whether ``select_topk`` of these operands runs in the kernel: on a TPU,
    a length of two or more whole tiles of ``ROWS`` (the table's and the
    kernel's side alike), bfloat16 index operands (one MXU pass a product;
    Mosaic loads no float16 on a v5e), index heads whose columns are whole
    128-lane tiles (``head_group``)."""
    L, heads, dim = qI.shape
    return (jax.default_backend() == "tpu"
            and rows == ROWS and (block or tile_side(L)) == ROWS
            and L % ROWS == 0 and 2 * ROWS <= L <= MAX_KERNEL_LENGTH
            and qI.dtype == jnp.bfloat16 and kI.dtype == qI.dtype
            and head_group(heads, dim) > 0)


def _image(x):
    """float32 -> int32 whose signed order is the floats' (no NaN):
    ``_ordered`` with the top bit flipped, as Mosaic compares signed."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


# sub-blocks the kernel's steps work in, small enough that a step's
# intermediates stay in the vector registers: a score product's [rows, keys],
# and the rows of a count's or the mask's step against a tile's ROWS keys
SCORE_ROWS, SCORE_KEYS, PASS_ROWS = 256, 128, 32


def _select_kernel(q_ref, w_ref, k_ref, keep_ref, tiles_ref, keys_ref,
                   weight_ref, count_ref, *, topk: int, head_dim: int,
                   group: int):
    """Row block ``i`` of the choice.  ``q_ref`` [ROWS, Hi di] and ``w_ref``
    [ROWS, Hi] of the block; ``k_ref`` [group, L, lanes] the index keys, copy
    ``a`` on the lanes of head ``a`` of a column block of ``group`` heads and
    zero on the others, so that a column block of ``q`` against it is that
    head's product; ``keep_ref`` [ROWS, L] int8 and ``tiles_ref`` [1, L /
    ROWS] int32 the block's rows of the mask and the table.  Scratch:
    ``keys_ref`` [L / ROWS, ROWS, ROWS] int32 the ordered images of the
    block's scores by key tile, ``weight_ref`` [Hi, ROWS, 128] float32 a
    head's weights along the lanes, ``count_ref`` [ROWS, 128] int32 a pass's
    count by lane."""
    i = pl.program_id(0)
    side, heads = w_ref.shape
    nk = keys_ref.shape[0]
    lanes = group * head_dim
    f32, i32 = jnp.float32, jnp.int32
    by_rows = range(0, side, PASS_ROWS)

    # -- the scores of key tiles 0 .. i, as ordered images -----------------
    w = w_ref[...]
    head_of = lax.broadcasted_iota(i32, w.shape, 1)
    for h in range(heads):
        weight_ref[h] = jnp.broadcast_to(
            jnp.where(head_of == h, w, 0.0).sum(axis=1, keepdims=True),
            weight_ref.shape[1:])

    def score(j, carry):
        def rows(r, carry):
            rows = pl.ds(pl.multiple_of(r * SCORE_ROWS, SCORE_ROWS),
                         SCORE_ROWS)
            for c in range(0, side, SCORE_KEYS):
                keys = pl.ds(pl.multiple_of(j * side + c, SCORE_KEYS),
                             SCORE_KEYS)
                total = jnp.zeros((SCORE_ROWS, SCORE_KEYS), f32)
                for h in range(heads):
                    g, a = divmod(h, group)
                    dots = _dot(q_ref[rows, g * lanes:(g + 1) * lanes],
                                k_ref[a, keys, :], _NT)
                    weight = weight_ref[h, rows, :]
                    total += jnp.maximum(dots, 0.0) * jnp.concatenate(
                        [weight] * (SCORE_KEYS // 128), axis=1)
                # a negative weight on a zero makes -0: one zero, so that
                # equal is equal
                keys_ref[j, rows, c:c + SCORE_KEYS] = _image(
                    jnp.where(total == 0, 0.0, total))
            return carry

        return lax.fori_loop(0, side // SCORE_ROWS, rows, carry)

    lax.fori_loop(0, i + 1, score, None)
    for r in by_rows:  # the causal mask, on the diagonal's tile alone
        image = keys_ref[i, r:r + PASS_ROWS, :]
        keys_ref[i, r:r + PASS_ROWS, :] = jnp.where(
            _keep(r, 0, image.shape, 1), image, _MASKED)

    # -- the threshold of a row: its topk-th largest image -----------------
    def count(flag):
        """[ROWS, 1] how many keys of a row ``flag(images, rows)`` holds
        for."""
        count_ref[...] = jnp.zeros(count_ref.shape, i32)

        def tile(j, carry):
            for r in by_rows:
                rows = slice(r, r + PASS_ROWS)
                c = flag(keys_ref[j, rows, :], rows).astype(i32)
                count_ref[rows, :] += sum(c[:, at:at + 128]
                                          for at in range(0, side, 128))
            return carry

        lax.fori_loop(0, i + 1, tile, None)
        return count_ref[...].sum(axis=1, keepdims=True)

    def threshold():
        """(the largest image with at least ``topk`` of a row's keys at or
        above it, built a bit at a time from the top, ``_MASKED`` for a row
        of fewer keys; how many equals a row may keep; by how many the
        fullest row's equals exceed that)."""
        def bit(p, best):
            # the unsigned image's next bit set: in the signed one, flipped
            trial = best ^ (jnp.int32(1) << (31 - p))
            enough = count(lambda t, rows: t >= trial[rows]) >= topk
            return jnp.where(enough, trial, best)

        kth = lax.fori_loop(0, 32, bit, jnp.full((side, 1), _MASKED, i32))
        room = topk - count(lambda t, rows: t > kth[rows])
        equal = count(lambda t, rows: (t == kth[rows]) & (t != _MASKED))
        return kth, room, (equal - room).max()

    # a block of at most topk causal keys keeps them all: no pass at all
    kth, room, over = lax.cond(
        (i + 1) * side > topk, threshold,
        lambda: (jnp.full((side, 1), _MASKED, i32),
                 jnp.zeros((side, 1), i32), jnp.int32(0)))

    # -- the mask and the table's row ---------------------------------------
    keep_ref[...] = jnp.zeros(keep_ref.shape, keep_ref.dtype)
    tile_of = lax.broadcasted_iota(i32, (1, nk), 1)

    def mark(j, kept, row):
        """The table's ``row`` with tile ``j`` live if it holds a kept pair."""
        return jnp.where(tile_of == j, kept.max(axis=1, keepdims=True).max(
            axis=0, keepdims=True), row)

    def every_equal(j, row):
        keys = pl.ds(pl.multiple_of(j * side, side), side)
        live = jnp.zeros((PASS_ROWS, side), i32)
        for r in by_rows:
            rows = slice(r, r + PASS_ROWS)
            t = keys_ref[j, rows, :]
            kept = (t >= kth[rows]) & (t != _MASKED)
            keep_ref[rows, keys] = kept.astype(keep_ref.dtype)
            live = jnp.maximum(live, kept.astype(i32))
        return mark(j, live, row)

    def first_equals(j, carry):
        """Equals to the lower positions while a row has room: a running
        count along the row as a product with a triangle of ones (exact: 0/1
        operands, float32 sums), the tiles before carried over."""
        before, row = carry
        t = keys_ref[j]
        level = (t == kth) & (t != _MASKED)
        ahead = lax.broadcasted_iota(i32, (side, side), 0) <= \
            lax.broadcasted_iota(i32, (side, side), 1)
        inside = _dot(level.astype(jnp.bfloat16), ahead.astype(jnp.bfloat16))
        kept = (t > kth) | (level & (before + inside <= room.astype(f32)))
        keep_ref[:, pl.ds(pl.multiple_of(j * side, side), side)] = \
            kept.astype(keep_ref.dtype)
        return (before + level.astype(f32).sum(axis=1, keepdims=True),
                mark(j, kept.astype(i32), row))

    row = jnp.zeros((1, nk), i32)
    tiles_ref[...] = lax.cond(
        over > 0,
        lambda: lax.fori_loop(0, i + 1, first_equals,
                              (jnp.zeros((side, 1), f32), row))[1],
        lambda: lax.fori_loop(0, i + 1, every_equal, row))


@functools.lru_cache(maxsize=None)
def _select_call(L, heads, dim, group, topk, interpret):
    """The ``pallas_call`` of ``_select_kernel`` at a shape, built once: every
    layer of a model calls the same object, which traces the kernel's body
    once (PERF.md, PR 36).  The row blocks are independent."""
    n = L // ROWS
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, head_dim=dim,
                          group=group),
        grid=(n,),
        in_specs=[pl.BlockSpec((ROWS, heads * dim), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS, heads), lambda i: (i, 0)),
                  pl.BlockSpec((group, L, group * dim), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((ROWS, L), lambda i: (i, 0)),
                   pl.BlockSpec((None, 1, n), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((L, L), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1, n), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n, ROWS, ROWS), jnp.int32),
                        pltpu.VMEM((heads, ROWS, 128), jnp.float32),
                        pltpu.VMEM((ROWS, 128), jnp.int32)],
        interpret=interpret, name="select_topk", **kwargs)


def select_in_kernel(qI, kI, w, topk: int, interpret: bool = False):
    """``select_topk`` as one Pallas kernel (``_select_kernel``), for a shape
    ``kernel_tiles`` takes; ``interpret`` runs it on the CPU (tests).  The same
    pairs' scores from the same bfloat16 operands with float32 sums, the exact
    ``topk`` with ties to the lower position; a score's head sum adds in head
    order, which need not be the order XLA's fusion adds in (float32, last
    bits), so a pair whose score lies that close to its row's threshold may
    fall on the other side of it than in the lax form."""
    L, heads, dim = qI.shape
    # a shape head_group refuses runs as one block of every head: right for
    # the interpreter, not sent to a chip by kernel_tiles
    group = head_group(heads, dim) or heads
    # copy a of the keys on head a's lanes of a column block, zero elsewhere
    by_head = jnp.einsum("ab,kd->akbd", jnp.eye(group, dtype=qI.dtype),
                         kI.astype(qI.dtype)).reshape(group, L, group * dim)
    keep, tiles = _select_call(L, heads, dim, group, topk, interpret)(
        qI.reshape(L, heads * dim), w.astype(jnp.float32), by_head)
    return keep, tiles.reshape(L // ROWS, L // ROWS)


def select_by_sort(scores, topk: int):
    """The definition: keep mask [L, L] int8 of ``scores`` [L, L] by
    ``lax.top_k`` of the causally masked scores, which puts the lower position
    first among equals."""
    L = scores.shape[0]
    causal = jnp.tril(jnp.ones((L, L), bool))
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, L))
    keep = jnp.zeros((L, L), bool).at[jnp.arange(L)[:, None], chosen].set(True)
    return (keep & causal).astype(jnp.int8)
