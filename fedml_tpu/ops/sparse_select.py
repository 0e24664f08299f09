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

The choice leaves here as a ``[L, L]`` int8 keep mask (causal: a kept pair
has ``s <= t``) and a ``[L / block, L / block]`` int32 table of the tiles
that hold a kept pair, which is what ``blockwise_attention(keep=)`` and
``flash_attention(keep=, tiles=)`` take.  Nothing here is differentiated: the
choice is discrete, and its inputs are cut from the gradient.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from fedml_tpu.ops.flash_attention import pick_block

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


def select_topk(qI, kI, w, topk: int, block: int = 0, rows: int = ROWS):
    """(keep [L, L] int8, tiles [L / block, L / block] int32) of the choice:
    ``keep[t, s]`` is 1 where ``s`` is in ``S_t``, ``tiles`` as
    ``live_tiles`` (``block`` 0: ``tile_side(L)``).  Scores and choice run
    ``rows`` queries at a time, so no [L, L] float32 reaches HBM."""
    qI, kI, w = (lax.stop_gradient(t) for t in (qI, kI, w))
    keep = _in_row_blocks(
        lambda qpos, q, ww: _choose_rows(_score_rows(q, kI, ww), qpos,
                                         topk).astype(jnp.int8),
        rows, qI, w)
    return keep, live_tiles(keep, block or tile_side(keep.shape[0]))


def select_by_sort(scores, topk: int):
    """The definition: keep mask [L, L] int8 of ``scores`` [L, L] by
    ``lax.top_k`` of the causally masked scores, which puts the lower position
    first among equals."""
    L = scores.shape[0]
    causal = jnp.tril(jnp.ones((L, L), bool))
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, L))
    keep = jnp.zeros((L, L), bool).at[jnp.arange(L)[:, None], chosen].set(True)
    return (keep & causal).astype(jnp.int8)
