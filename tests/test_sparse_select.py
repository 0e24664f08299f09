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


# -- the kernel, in Pallas's interpreter ------------------------------------
# ``select_in_kernel`` is what ``select_topk`` runs on a TPU where the shape
# tiles (``kernel_tiles``): a row block of 512 against its causal key tiles.

def exact_inputs(L, kind, heads=2, dim=64, seed=0):
    """Integer-valued index operands (bf16 holds them, and every float32 sum
    of their products is exact in any order), so the kernel, the lax form and
    the definition see the same scores to the bit, ties all over."""
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 3)
    qI = jnp.round(jax.random.normal(ks[0], (L, heads, dim)))
    kI = jnp.round(jax.random.normal(ks[1], (L, dim)))
    w = jnp.round(2 * jnp.abs(jax.random.normal(ks[2], (L, heads))))
    if kind == "negative_w":  # scores of both signs, and -0 where relu is 0
        w = w * jnp.where(jnp.arange(heads) % 2 == 0, 1.0, -1.0)
    elif kind == "all_negative_w":  # no score above 0: the relu's zeros tie
        w = -w
    elif kind == "zero_queries":  # whole rows of exact zeros
        qI = qI.at[L // 2:].set(0.0)
    return qI.astype(jnp.bfloat16), kI.astype(jnp.bfloat16), w


def in_kernel(qI, kI, w, topk):
    return ss.select_in_kernel(qI, kI, w, topk, interpret=True)


def assert_a_choice(keep, tiles, L, topk):
    """What holds for every choice: int8 [L, L], ``min(t + 1, topk)`` causal
    keys a row, nothing above the diagonal, the table ``live_tiles``'s."""
    assert keep.dtype == jnp.int8 and keep.shape == (L, L)
    kept = np.asarray(keep).sum(axis=1)
    assert (kept == np.minimum(np.arange(L) + 1, topk)).all()
    assert not np.triu(np.asarray(keep), 1).any()
    n = L // ss.ROWS
    assert tiles.dtype == jnp.int32 and tiles.shape == (n, n)
    by_hand = np.asarray(ss.live_tiles(keep, ss.ROWS))
    assert (np.asarray(tiles) == by_hand).all()
    assert not np.triu(np.asarray(tiles), 1).any()


@pytest.mark.parametrize("kind", ["few_values", "negative_w"])
@pytest.mark.parametrize("topk", [256, 512, 1536])
@pytest.mark.parametrize("L", [1024, 2048])
def test_the_kernels_choice_is_the_definitions_set(L, topk, kind):
    qI, kI, w = exact_inputs(L, kind)
    want = ss.select_by_sort(ss.index_scores(qI, kI, w), topk)
    keep, tiles = in_kernel(qI, kI, w, topk)
    assert (np.asarray(keep) == np.asarray(want)).all()
    assert_a_choice(keep, tiles, L, topk)
    lax_keep, lax_tiles = ss.select_in_lax(qI, kI, w, topk)
    assert (np.asarray(keep) == np.asarray(lax_keep)).all()
    assert (np.asarray(tiles) == np.asarray(lax_tiles)).all()


@pytest.mark.parametrize("kind", ["all_negative_w", "zero_queries"])
def test_the_kernel_gives_equals_over_the_room_to_the_lower_positions(kind):
    """Under negative weights no score is above zero and the relu's zeros, far
    more than ``topk`` of them a row, are the largest; rows of zero queries
    score nothing but zeros: the first ``topk`` positions among them win."""
    L, topk = 1024, 128
    qI, kI, w = exact_inputs(L, kind)
    scores = np.asarray(ss.index_scores(qI, kI, w))
    t = L - 1  # a row of the second block
    zeros = np.flatnonzero(scores[t, :t + 1] == 0)
    assert scores[t].max() <= 0 and len(zeros) > 2 * topk
    keep, tiles = in_kernel(qI, kI, w, topk)
    assert (np.flatnonzero(np.asarray(keep)[t]) == zeros[:topk]).all()
    want = ss.select_by_sort(jnp.asarray(scores), topk)
    assert (np.asarray(keep) == np.asarray(want)).all()
    assert_a_choice(keep, tiles, L, topk)


def test_a_kernels_block_of_at_most_topk_rows_keeps_its_causal_keys():
    L, topk = 2048, 1536  # blocks 0 to 2 need no threshold, block 3 does
    qI, kI, w = exact_inputs(L, "negative_w", seed=3)
    keep, tiles = in_kernel(qI, kI, w, topk)
    causal = np.tril(np.ones((L, L), np.int8))
    assert (np.asarray(keep)[:topk] == causal[:topk]).all()
    assert (np.asarray(keep)[topk:].sum(axis=1) == topk).all()
    assert (np.asarray(tiles) == np.tril(np.ones((4, 4), np.int32))).all()


def test_the_kernel_runs_under_vmap():
    L, topk = 1024, 256
    batch = [exact_inputs(L, "few_values", seed=s) for s in (0, 1)]
    qI, kI, w = (jnp.stack(t) for t in zip(*batch))
    keep, tiles = jax.vmap(lambda *i: in_kernel(*i, topk))(qI, kI, w)
    assert keep.shape == (2, L, L) and tiles.shape == (2, 2, 2)
    for b, one in enumerate(batch):
        alone, table = in_kernel(*one, topk)
        assert (np.asarray(keep[b]) == np.asarray(alone)).all()
        assert (np.asarray(tiles[b]) == np.asarray(table)).all()
    assert (np.asarray(keep[0]) != np.asarray(keep[1])).any()


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``select_topk`` as a TPU would run it: the shape test reads a TPU, and
    the kernel it then calls runs in the interpreter; yields the calls."""
    called, kernel = [], ss.select_in_kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ss, "select_in_kernel", lambda *i: (
        called.append(i[0].shape), kernel(*i, interpret=True))[1])
    return called


def test_the_kernels_choice_passes_no_gradient(on_a_tpu):
    qI, kI, w = exact_inputs(1024, "few_values")
    grads = jax.grad(lambda *i: ss.select_topk(*i, 256)[0].astype(
        jnp.float32).sum(), argnums=(0, 1, 2))(qI, kI, w)
    assert on_a_tpu == [(1024, 2, 64)]
    assert all(not np.asarray(g.astype(jnp.float32)).any() for g in grads)


# of the pairs of a row, the share the kernel may decide otherwise than the
# lax form on inexact scores: a head sum's or a product's last bits, at a
# score next to the row's threshold
PAIRS_OFF = 1e-4


@pytest.mark.parametrize("heads, dim", [(4, 64), (2, 128)])
def test_on_inexact_scores_the_kernel_differs_in_a_pair_in_ten_thousand(
        heads, dim):
    L, topk = 1024, 256
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    qI = jax.random.normal(ks[0], (L, heads, dim)).astype(jnp.bfloat16)
    kI = jax.random.normal(ks[1], (L, dim)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[2], (L, heads))
    keep, tiles = in_kernel(qI, kI, w, topk)
    assert_a_choice(keep, tiles, L, topk)
    lax_keep, _ = ss.select_in_lax(qI, kI, w, topk)
    off = (np.asarray(keep) != np.asarray(lax_keep)).sum()
    assert off <= PAIRS_OFF * L * (L + 1) / 2


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("backend, L, heads, dim, dtype, block, rows, runs", [
    pytest.param("tpu", 8192, 16, 64, BF16, 0, 512, True, id="the_cell"),
    pytest.param("tpu", 1024, 2, 128, BF16, 512, 512, True, id="two_tiles"),
    pytest.param("cpu", 8192, 16, 64, BF16, 0, 512, False, id="off_the_tpu"),
    pytest.param("tpu", 512, 16, 64, BF16, 0, 512, False, id="one_tile"),
    pytest.param("tpu", 8192 + 256, 16, 64, BF16, 0, 512, False, id="ragged"),
    pytest.param("tpu", 700, 16, 64, BF16, 0, 512, False, id="no_tiles"),
    pytest.param("tpu", 32768, 16, 64, BF16, 0, 512, False, id="too_long"),
    pytest.param("tpu", 8192, 16, 64, F32, 0, 512, False, id="float32"),
    pytest.param("tpu", 8192, 16, 64, jnp.float16, 0, 512, False,
                 id="float16"),  # Mosaic loads none on a v5e
    pytest.param("tpu", 8192, 3, 64, BF16, 0, 512, False, id="half_a_tile"),
    pytest.param("tpu", 8192, 16, 48, BF16, 0, 512, False, id="ragged_heads"),
    pytest.param("tpu", 8192, 16, 64, BF16, 256, 512, False, id="finer_table"),
    pytest.param("tpu", 8192, 16, 64, BF16, 0, 128, False, id="finer_rows"),
])
def test_the_shape_test_reads_what_the_call_can_see(
        monkeypatch, backend, L, heads, dim, dtype, block, rows, runs):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    qI = jax.ShapeDtypeStruct((L, heads, dim), dtype)
    kI = jax.ShapeDtypeStruct((L, dim), dtype)
    assert ss.kernel_tiles(qI, kI, block, rows) is runs
    n = L // ss.tile_side(L) if not block else L // block
    assert ss.tiles_scored(qI, kI, block, rows) == (
        n * (n + 1) // 2 if runs else n * n)


def test_select_topk_runs_the_form_the_shape_test_names(on_a_tpu):
    """Where the shape test says so ``select_topk`` is the kernel
    (interpreted here), at every other shape the lax form."""
    L, topk = 1024, 256
    qI, kI, w = exact_inputs(L, "few_values")
    keep, tiles = ss.select_topk(qI, kI, w, topk)
    assert on_a_tpu == [(L, 2, 64)]
    lax_keep, lax_tiles = ss.select_in_lax(qI, kI, w, topk)
    assert (np.asarray(keep) == np.asarray(lax_keep)).all()
    assert (np.asarray(tiles) == np.asarray(lax_tiles)).all()
    ss.select_topk(qI.astype(jnp.float32), kI.astype(jnp.float32), w, topk)
    ss.select_topk(qI[:700], kI[:700], w[:700], topk)
    ss.select_topk(qI[:512], kI[:512], w[:512], topk)
    ss.select_topk(qI, kI, w, topk, rows=128)
    assert on_a_tpu == [(L, 2, 64)]


def test_off_the_tpu_select_topk_is_the_lax_form(monkeypatch):
    monkeypatch.setattr(ss, "select_in_kernel", None)  # never called
    qI, kI, w = exact_inputs(1024, "few_values")
    assert not ss.kernel_tiles(qI, kI)
    keep, _ = ss.select_topk(qI, kI, w, 256)
    assert (np.asarray(keep)
            == np.asarray(ss.select_in_lax(qI, kI, w, 256)[0])).all()


def test_the_signed_image_is_the_ordered_image_with_the_top_bit_flipped():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    image = np.asarray(ss._image(x)).astype(np.int64)
    assert (np.diff(image) > 0).all() and image.min() > ss._MASKED
    unsigned = np.asarray(ss._ordered(x)).astype(np.int64)
    assert (image + 2 ** 31 == unsigned).all()
