"""The expert layer's two row transfers, between token space ``[T, h]`` and
the row buffer ``[C, h]`` that the grouped products read and write.

A ``Route`` says where the rows go: the (token, slot) assignments sorted by
the local index of their expert, held experts first, so that the buffer's
first ``group_sizes.sum()`` rows are the held assignments, an expert after
another and by token inside an expert.  Then

    to_buffer(x)[c]     = x[tok[c]]                     where row ``c`` is live, else 0
    from_buffer(buf)[t] = sum over the slots j of token t that are here
                          of buf[rank[t, j]]            (added in float32)

are each other's transpose, and each is the other's backward (a
``custom_vjp`` pair): a held assignment is one live row, and the rows a
token's slots name are its own.  Neither builds an array of ``T x k`` rows
on a TPU: ``to_buffer`` is one gather of ``C`` indices, and ``from_buffer``
is one pass of a Pallas kernel over token tiles.

The kernel uses the order the sort gives.  Inside an expert's group the rows
are sorted by token and a token has at most one, so the rows a tile of
``TOKEN_TILE`` tokens wants from one expert are one contiguous range of the
buffer, at most ``TOKEN_TILE`` long and about ``C / (experts x tiles)`` under
a level router.  A grid step takes one window of ``window`` rows an expert
(a ``BlockSpec`` whose element offset comes from the prefetched range starts,
so the pipeline fetches a tile's windows while the tile before is computed),
zeroes the rows past the routed count (the grouped product never wrote them
and they may hold anything), and places all of them with one product on the
MXU: a [tile, experts x window] matrix of ones where ``rank[t, j]`` names the
window's row, times the windows.  A product of a one with a row is the row,
and the sum has at most one term an expert, so the result is the float32 sum
of the token's held rows (added by expert, where the lax form adds by slot).
A range longer than its window (a router that is not level) takes further
rounds inside the same step, a window an expert copied by hand; a second
grid axis for them cost a level router five steps for one (0.46 ms against
0.21 at the decoder cells' shapes, PERF.md §6, PR 36).

Off the TPU and at shapes the kernel does not tile (``window_rows``), the lax
form runs: a gather of ``T x k`` rows, a select and a sum.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKEN_TILE = 256  # tokens a grid step sums
ROW_ALIGN = 16  # a window starts on a whole (16, 128) tile of a bf16 buffer
MIN_WINDOW = 64
WINDOWS_BYTES = 4 * 2**20  # of VMEM for one step's windows, all experts
VMEM_LIMIT_BYTES = 64 * 2**20


class Route(NamedTuple):
    """Where the rows of a call go.  ``C`` buffer rows, ``T`` tokens of ``k``
    slots, ``E`` experts held."""

    tok: jax.Array  # [C] int32: the token whose row buffer row c holds
    rank: jax.Array  # [T, k] int32: the buffer row of a slot; any row < C where not here
    local: jax.Array  # [T, k] int32: the local index of a slot's expert; E: not here
    group_sizes: jax.Array  # [E] int32: rows of each held expert

    @property
    def here(self):  # [T, k] bool: the slot's expert is held
        return self.local < self.group_sizes.shape[0]

    @property
    def row_live(self):  # [C] bool: row c is a held assignment
        return jnp.arange(self.tok.shape[0]) < self.group_sizes.sum()


def sorted_route(local, order, rank, group_sizes, capacity: int) -> Route:
    """The route through ``capacity`` rows of the assignments ``local`` [T, k]
    (each slot's local expert index, ``E`` where not held) sorted by it:
    ``order`` [T x k] the stable sort, ``rank`` [T, k] its inverse,
    ``group_sizes`` [E] the held experts' counts, ``capacity`` at least their
    sum."""
    return Route(tok=order[:capacity] // local.shape[1],
                 rank=jnp.minimum(rank, capacity - 1), local=local,
                 group_sizes=group_sizes)


@jax.custom_vjp
def to_buffer(x, route: Route):
    """``x`` [T, h] -> [C, h]: row ``c`` is ``x[tok[c]]``, zero where not live."""
    return jnp.where(route.row_live[:, None], x[route.tok], 0)


@jax.custom_vjp
def from_buffer(buf, route: Route):
    """``buf`` [C, h] -> [T, h]: a token's rows, those its slots that are here
    name, added in float32 and returned in ``buf``'s dtype."""
    tokens, width = route.rank.shape[0], buf.shape[1]
    window = window_rows(tokens, buf.shape[0], width,
                         route.group_sizes.shape[0], buf.dtype.itemsize)
    if jax.default_backend() == "tpu" and window:
        return from_buffer_windows(buf, route, window)
    return from_buffer_lax(buf, route)


to_buffer.defvjp(lambda x, route: (to_buffer(x, route), route),
                 lambda route, g: (from_buffer(g, route), None))
from_buffer.defvjp(lambda buf, route: (from_buffer(buf, route), route),
                   lambda route, g: (to_buffer(g, route), None))


def from_buffer_lax(buf, route: Route):
    """``from_buffer`` as a gather of every slot's row, a select and a sum."""
    T, k = route.rank.shape
    rows = buf[route.rank.reshape(T * k)].reshape(T, k, buf.shape[1])
    return jnp.where(route.here[..., None], rows, 0).astype(
        jnp.float32).sum(axis=1).astype(buf.dtype)


def window_rows(tokens: int, capacity: int, width: int, held: int,
                itemsize: int) -> Optional[int]:
    """Rows of one expert's window in a step of the kernel, or None where it
    does not take the shape: whole token tiles, whole lanes, a buffer of whole
    row tiles no shorter than a window, and a step's windows inside
    ``WINDOWS_BYTES``.  The window is what a full buffer would hold for one
    tile and expert, at least ``MIN_WINDOW``: a level router in a buffer of
    twice its share fills half of it."""
    if tokens % TOKEN_TILE or capacity % ROW_ALIGN or width % 128:
        return None
    share = -(-capacity // (held * (tokens // TOKEN_TILE)))
    window = max(MIN_WINDOW, -(-share // ROW_ALIGN) * ROW_ALIGN)
    if window > capacity or held * window * width * itemsize > WINDOWS_BYTES:
        return None
    return window


def range_starts(route: Route):
    """[(tiles + 1) x E] int32, flat: entry ``i * E + e`` is the first buffer
    row of expert ``e`` that belongs to a token of tile ``i`` or later, so
    the rows tile ``i`` wants from ``e`` run from entry ``i * E + e`` up to
    entry ``(i + 1) * E + e``; the last entry is the count of routed rows."""
    (T, k), held = route.local.shape, route.group_sizes.shape[0]
    in_tile = (route.local.reshape(T // TOKEN_TILE, TOKEN_TILE * k, 1)
               == jnp.arange(held)).sum(axis=1, dtype=jnp.int32)
    first = jnp.cumsum(route.group_sizes) - route.group_sizes
    before = jnp.concatenate([jnp.zeros((1, held), jnp.int32),
                              jnp.cumsum(in_tile, axis=0)])
    return (first + before).astype(jnp.int32).reshape(-1)


def _range(starts, i, e, held):
    """(first row, one past the last row, the first rounded down to a row
    tile) of the range tile ``i`` wants from expert ``e``."""
    lo, hi = starts[i * held + e], starts[(i + 1) * held + e]
    return lo, hi, lo & -ROW_ALIGN  # a power of two


def _window_first(base, r, window, capacity):
    """The first row of round ``r``'s window over a range that starts (on a
    row tile) at ``base``: round ``r`` reads the range's rows in ``[base + r
    w, base + (r + 1) w)``, and a window never leaves the buffer."""
    return jnp.minimum(base + r * window, capacity - window)


def _windows_kernel(starts, slots_ref, *refs, held, window, capacity):
    wins, buf_ref, out_ref = refs[:held], refs[held], refs[held + 1]
    acc_ref, rows_ref, arrived = refs[held + 2:]
    i = pl.program_id(0)
    ranges = [_range(starts, i, e, held) for e in range(held)]
    live = starts[pl.num_programs(0) * held + held - 1]  # rows routed
    slot_of = [pl.ds(e * window, window) for e in range(held)]
    # a router that is not level makes a range longer than its window: the
    # rest of it takes further rounds, a window a round
    rounds = functools.reduce(jnp.maximum, (
        jnp.where(hi > lo, jax.lax.div(hi - base + window - 1, window), 1)
        for lo, hi, base in ranges))

    def entries(axis):
        """The index along ``axis`` of each entry of ``rows_ref``'s rows, as
        a column (axis 0) or a row (axis 1)."""
        shape = (held * window, 1) if axis == 0 else (1, held * window)
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def by_expert(at, values):
        """``values[e]`` (int32 scalars) on the ``window`` entries of expert
        ``e``, in ``at``'s shape."""
        out = jnp.full(at.shape, values[0], jnp.int32)
        for e in range(1, held):
            out = jnp.where(at >= e * window, values[e], out)
        return out

    def one_round(r, carry):
        firsts = [_window_first(base, r, window, capacity)
                  for _, _, base in ranges]

        @pl.when(r == 0)  # the windows the pipeline fetched
        def _():
            for e in range(held):
                rows_ref[slot_of[e], :] = wins[e][...]

        @pl.when(r > 0)  # copied here and now, where the range has more
        def _():
            copies = [(hi > base + r * window, pltpu.make_async_copy(
                buf_ref.at[pl.ds(pl.multiple_of(firsts[e], ROW_ALIGN),
                                 window), :],
                rows_ref.at[slot_of[e], :], arrived.at[e]))
                for e, (_, hi, base) in enumerate(ranges)]
            for more, copy in copies:
                pl.when(more)(copy.start)
            for more, copy in copies:
                pl.when(more)(copy.wait)

        # the row each entry of ``rows_ref`` holds, less its place in it
        offsets = [first - e * window for e, first in enumerate(firsts)]

        # a row past the routed count was never written and may hold a NaN; a
        # row another expert owns is finite and meets a zero of ``hit``
        @pl.when(functools.reduce(jnp.maximum, firsts) + window > live)
        def _():
            at = entries(0)
            rows_ref[...] = jnp.where(at + by_expert(at, offsets) < live,
                                      rows_ref[...], 0)

        at = entries(1)
        lo = by_expert(at, [jnp.maximum(lo, base + r * window) - offsets[e]
                            for e, (lo, _, base) in enumerate(ranges)])
        hi = by_expert(at, [jnp.minimum(hi, base + (r + 1) * window)
                            - offsets[e]
                            for e, (_, hi, base) in enumerate(ranges)])
        # a slot that is not here holds -1
        row = jnp.where((at >= lo) & (at < hi), at + by_expert(at, offsets),
                        -2)
        hit = functools.reduce(jnp.logical_or, (
            slots_ref[:, j:j + 1] == row for j in range(slots_ref.shape[1])))
        total = jnp.dot(jnp.where(hit, 1, 0).astype(rows_ref.dtype),
                        rows_ref[...], preferred_element_type=jnp.float32)

        @pl.when(r == 0)
        def _():
            out_ref[...] = total.astype(out_ref.dtype)

            @pl.when(rounds > 1)
            def _():
                acc_ref[...] = total

        @pl.when(r > 0)
        def _():
            acc_ref[...] += total

            @pl.when(r == rounds - 1)
            def _():
                out_ref[...] = acc_ref[...].astype(out_ref.dtype)

        return carry

    jax.lax.fori_loop(0, rounds, one_round, 0)


def from_buffer_windows(buf, route: Route, window: int,
                        interpret: bool = False):
    """``from_buffer`` as the kernel, with windows of ``window`` rows.  The
    tokens must be whole tiles of ``TOKEN_TILE``, the buffer and the window
    whole row tiles of ``ROW_ALIGN``, the window no longer than the buffer."""
    (T, k), (capacity, width) = route.rank.shape, buf.shape
    held = route.group_sizes.shape[0]
    if (T % TOKEN_TILE or window % ROW_ALIGN or capacity % ROW_ALIGN
            or not 0 < window <= capacity):
        raise ValueError(
            f"from_buffer_windows: {T} tokens in tiles of {TOKEN_TILE}, "
            f"windows of {window} rows in a buffer of {capacity} (both in "
            f"row tiles of {ROW_ALIGN})")
    slots = jnp.where(route.here, route.rank, -1).astype(jnp.int32)
    return _windows_call(T, k, capacity, width, held, window, buf.dtype,
                         interpret)(range_starts(route), slots,
                                    *[buf] * (held + 1))


@functools.lru_cache(maxsize=None)
def _windows_call(T, k, capacity, width, held, window, dtype, interpret):
    """The kernel's ``pallas_call`` for a shape, built once: a layer calls it
    four times while it is traced (twice forward, once a dead forward in the
    backward, once backward) and a model a layer, and a call of the same
    object with the same shapes traces the kernel's body no second time."""
    def window_of(e):
        def index(i, starts):
            first = _window_first(_range(starts, i, e, held)[2], 0, window,
                                  capacity)
            return pl.multiple_of(first, ROW_ALIGN), 0
        return pl.BlockSpec((pl.Element(window), pl.Element(width)), index)

    return pl.pallas_call(
        functools.partial(_windows_kernel, held=held, window=window,
                          capacity=capacity),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // TOKEN_TILE,),
            in_specs=[pl.BlockSpec((TOKEN_TILE, k), lambda i, s: (i, 0)),
                      *(window_of(e) for e in range(held)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TOKEN_TILE, width), lambda i, s: (i, 0)),
            scratch_shapes=[pltpu.VMEM((TOKEN_TILE, width), jnp.float32),
                            pltpu.VMEM((held * window, width), dtype),
                            pltpu.SemaphoreType.DMA((held,))]),
        out_shape=jax.ShapeDtypeStruct((T, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="from_buffer")
