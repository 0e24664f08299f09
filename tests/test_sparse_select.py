"""``ops/sparse_select.py``: the choice the model runs (a threshold by
bisection, ties by a running count) against the definition (``lax.top_k`` of
the causally masked scores, the lower position first among equals), float32
on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import sparse_select as ss


def index_inputs(L, kind, heads=2, dim=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 3)
    qI = jax.random.normal(ks[0], (L, heads, dim))
    kI = jax.random.normal(ks[1], (L, dim))
    w = jnp.abs(jax.random.normal(ks[2], (L, heads)))
    if kind == "negative_w":  # scores of both signs, and -0 where relu is 0
        w = w * jnp.where(jnp.arange(heads) % 2 == 0, 1.0, -1.0)
    elif kind == "zero_keys":  # an index key orthogonal to every index query
        kI = kI.at[::3].set(0.0)
    elif kind == "zero_queries":  # a whole row of exact zeros
        qI = qI.at[L // 2:].set(0.0)
    elif kind == "few_values":  # every score one of a handful: ties all over
        qI, kI, w = jnp.round(qI), jnp.round(kI), jnp.round(w)
    elif kind == "bf16_operands":  # the products' operands in 16 bits
        qI, kI = qI.astype(jnp.bfloat16), kI.astype(jnp.bfloat16)
    return qI, kI, w


# (length, topk, rows a block): below, at and above topk; a length of whole
# 128-wide pieces (the running count as a product) and ragged ones
SHAPES = [(16, 32, 512), (32, 32, 8), (96, 32, 32), (256, 48, 64),
          (200, 16, 512)]
KINDS = ["random", "negative_w", "zero_keys", "zero_queries", "few_values",
         "bf16_operands"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("L, topk, rows", SHAPES)
def test_the_choice_is_the_definitions_set(L, topk, rows, kind):
    qI, kI, w = index_inputs(L, kind)
    want = ss.select_by_sort(ss.index_scores(qI, kI, w, rows), topk)
    keep, _ = ss.select_topk(qI, kI, w, topk, rows=rows)
    assert keep.dtype == jnp.int8 and keep.shape == (L, L)
    assert (np.asarray(keep) == np.asarray(want)).all()
    kept = np.asarray(keep).sum(axis=1)
    assert (kept == np.minimum(np.arange(L) + 1, topk)).all()
    assert not np.triu(np.asarray(keep), 1).any()  # causal


def test_equal_scores_go_to_the_lower_position():
    L, topk = 24, 4
    qI = jnp.zeros((L, 1, 2))  # every score is exactly zero
    keep, _ = ss.select_topk(qI, jnp.ones((L, 2)), jnp.ones((L, 1)), topk)
    want = np.tril(np.ones((L, L), np.int8))
    want[:, topk:] = 0
    assert (np.asarray(keep) == want).all()


@pytest.mark.parametrize("kind", ["random", "few_values"])
def test_scores_are_the_equation_and_hold_one_zero(kind):
    qI, kI, w = index_inputs(40, kind)
    w = w * jnp.where(jnp.arange(2) % 2 == 0, 1.0, -1.0)
    got = ss.index_scores(qI, kI, w, rows=8)
    want = sum(np.asarray(w)[:, j:j + 1] * np.maximum(
        np.asarray(qI)[:, j] @ np.asarray(kI).T, 0) for j in range(2))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not np.signbit(np.asarray(got)[np.asarray(got) == 0]).any()


@pytest.mark.parametrize("k", [1, 5, 64])
def test_kth_largest_is_the_sorted_rows_kth(k):
    x = jax.random.normal(jax.random.PRNGKey(k), (6, 64))
    x = x.at[0].set(jnp.round(x[0]))  # a row with equal values
    got = ss._kth_largest(ss._ordered(x), k)
    want = ss._ordered(jnp.sort(x, axis=1)[:, -k])
    assert (np.asarray(got) == np.asarray(want)).all()


def test_the_ordered_image_keeps_the_floats_order():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    image = np.asarray(ss._ordered(x)).astype(np.uint64)
    assert (np.diff(image.astype(np.int64)) > 0).all()
    assert image.min() > 0  # 0 stands for a masked key, under every score


@pytest.mark.parametrize("L", [128, 384, 100])
def test_running_count_is_a_cumulative_sum(L):
    flags = jax.random.bernoulli(jax.random.PRNGKey(L), 0.4, (5, L))
    want = np.cumsum(np.asarray(flags), axis=1)
    assert (np.asarray(ss._running_count(flags)) == want).all()


@pytest.mark.parametrize("L, block", [(64, 16), (96, 32), (48, 48)])
def test_the_tile_table_marks_the_tiles_that_hold_a_kept_pair(L, block):
    qI, kI, w = index_inputs(L, "zero_keys")
    # keys nothing picks once the row is longer than topk: an empty tile
    kI = kI.at[block:2 * block].set(0.0) if L >= 2 * block else kI
    keep, tiles = ss.select_topk(qI, kI, w, 8, block=block)
    n = L // block
    assert tiles.shape == (n, n) and tiles.dtype == jnp.int32
    by_hand = np.asarray(keep).reshape(n, block, n, block).any(axis=(1, 3))
    assert (np.asarray(tiles) == by_hand).all()
    assert not np.triu(np.asarray(tiles), 1).any()
    if n > 2:
        assert not by_hand[n - 1, 1]  # a tile below the diagonal is empty


def test_tile_side_is_the_kernels_block_or_the_whole_length():
    assert ss.tile_side(8192) == 512 and ss.tile_side(1024) == 512
    assert ss.tile_side(32) == 32 and ss.tile_side(700) == 700
    assert ss.tile_side(768) == 256  # as pick_block, so the table fits


def test_the_choice_passes_no_gradient():
    qI, kI, w = index_inputs(32, "random")
    grads = jax.grad(lambda *i: ss.select_topk(*i, 8)[0].astype(
        jnp.float32).sum(), argnums=(0, 1, 2))(qI, kI, w)
    assert all(not np.asarray(g).any() for g in grads)
