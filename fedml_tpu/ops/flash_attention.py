"""Pallas TPU flash attention: a forward and a backward kernel.

The hot op of the transformer family (``models/transformer.py``):
softmax(QKᵀ/√d)V computed blockwise in VMEM with online-softmax
accumulation.  No [L, L] score or probability block ever reaches HBM, in
the forward or in the backward: the forward saves only ``o`` and the
per-row log-sum-exp ``lse`` ([H, L] float32), and the backward recomputes
``p = exp(s - lse)`` per block pair.  This is the single-device attention
path (``_default_attn``) and the per-step local attention of
``parallel.ring_attention.ring_flash_attention``; the lax ring keeps its
own blockwise inner loop.

Head sizes: q and k of one size ``D`` and v (so ``o`` and ``dO``) of the
same or of another, ``Dv``.  On a chip ``D = Dv`` of 128 or a divisor of it
(``head_group``), or two sizes whose columns of 1, 2 or 4 heads are whole
128-lane tiles in both views: a latent-attention layer's 192 / 128 runs two
heads a block, 384 lanes of q and k beside 256 of v, with no padded or
repeated copy of either in HBM.

Layout: the kernels read the model's own [L, H, D] arrays as [L, H·D]
(a free reshape: no head transpose in HBM) in column blocks of
``group`` heads, 128 lanes wide where the head size divides 128.  A head
inside a block is picked with lane masks, never with lane slices: a
masked ``q`` contracted over all 128 lanes against ``k`` is that head's
``q kᵀ`` (the MXU pads a 64-deep contraction to 128 anyway), and
``p @ v`` over the block's 128 columns is kept on that head's lanes only.
Scores, probabilities and every running sum are float32; ``p`` and ``ds``
are rounded to the input dtype only as matmul operands.

Grids (a ``vmap`` prepends its batch axis).  ``flash_fwd`` runs (head
blocks, q blocks) with that head block's k and v whole in VMEM, and walks
the kv blocks in the kernel's own loop.  Its running max ``m``, sum ``l``
and accumulator ``acc`` are float32 VMEM scratch that each block pair
updates in place, as the backward's sums are: ``m`` and ``l`` [block, 128]
with every lane alike, so that they broadcast over the score block's and
the accumulator's lanes as whole vregs, ``acc`` [block, Wv]; each head of
the column block resets them.  A loop carry of the three cost a copy a
block pair, and a branch that must yield it (a choice's per-tile skip) a
copy in and out: at [8192, 32 q heads on 4, 128] the forward over chosen
keys took 7.45 ms and the causal one 5.02, in scratch 4.55 and 4.04
(PERF.md §6, PR 42).  ``flash_bwd`` runs
(head blocks, kv blocks) with q and dO whole in VMEM (in one buffer each where two
would not fit: their block changes only with the head block), walks the
q blocks, and computes ``s``, ``p``, ``dp`` and ``ds`` once a block pair
for all three gradients: five matmuls and one ``exp`` pass.  It works on
the transposed block ``sᵀ = k qᵀ``, so that ``lse`` and ``delta``
broadcast as lane-dense rows and ``dv += pᵀ dO``, ``dk += dsᵀ q`` take
the block as computed; ``dq += ds k`` contracts the kv dim of ``dsᵀ``
and ``k``, the one transposed operand.  The three sums are float32
accumulators in VMEM that every matmul adds to in place (a loop carry
costs a copy a block pair): ``dk`` and ``dv`` [block, 128], zeroed and
written every grid step, ``dq`` [L, 128], which the kv axis of the grid
(sequential) carries from step to step and the last step scales, casts
and writes, so no float32 gradient reaches HBM.  Under a causal mask the
loops' bounds leave out the blocks above the diagonal, and only the
blocks the diagonal crosses apply the mask.

Which pairs count is said in one of four ways: nothing (every pair),
``causal`` (the shapes alone), ``causal`` with a ``window``, or a **choice**,
``keep`` [L, L] int8 with ``tiles``, its table of the block pairs that hold a
kept pair (``ops/sparse_select.py``: the keys a learned indexer picks a
query).  The first three are known from the shapes, and the loops' bounds
leave whole blocks out.  A choice is data: both kernels read the table from
SMEM and step over a block pair it marks empty, and a live pair masks by its
[block, block] tile of ``keep``, which holds the causal mask too.  The
forward takes the tiles of its q block along a leading dim ([nk, bq, bk]), the
backward those of its kv block transposed ([nq, bk, bq], as it works on
``s^T``); each is one relayout of ``keep`` in HBM.  No row of k or v is
gathered: a chosen pair costs what a causal pair costs, and a tile's masked
pairs are computed and dropped.

``interpret=True`` runs the same kernels on the CPU (tests);
``blockwise_attention`` remains the lax fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# scoped VMEM the kernels may use (the v5e has 128 MiB): one side of the
# attention is whole in VMEM, double-buffered
VMEM_LIMIT = 96 * 1024 * 1024

# contract the last dim of both operands: a @ b.T without a transpose
_NT = (((1,), (1,)), ((), ()))
# contract the first dim of both: a.T @ b, the one transposed operand
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    # 16-bit operands multiply exactly in one MXU pass whatever precision
    # the caller's config asks for (Mosaic refuses "highest" on bf16);
    # float32 operands follow the config
    precision = jax.lax.Precision.DEFAULT if a.dtype.itemsize == 2 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def head_group(num_heads: int, head_dim: int, v_head_dim: int = 0) -> int:
    """Heads per column block of the [L, H·D] view, 0 if the kernels do
    not take the shape on a TPU: a block is ``head_dim`` lanes wide when
    that is whole 128-lane tiles, else 128 lanes of ``128 // head_dim``
    heads.  Where v's head size differs from q's and k's, the fewest heads
    (1, 2 or 4) whose columns are whole tiles in both views: two heads of
    192 and 128 make blocks of 384 and 256 lanes."""
    if v_head_dim and v_head_dim != head_dim:
        return next((g for g in (1, 2, 4) if num_heads % g == 0
                     and g * head_dim % LANES == 0
                     and g * v_head_dim % LANES == 0), 0)
    if head_dim % LANES == 0:
        return 1
    group = LANES // head_dim
    if LANES % head_dim == 0 and num_heads % group == 0:
        return group
    return 0


def _head_lanes(shape, a: int, head_dim: int, group: int):
    """Mask of head ``a``'s lanes in a [rows, group·head_dim] block."""
    if group == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= a * head_dim) & (lane < (a + 1) * head_dim)


def _lanes_of(qk_shape, v_shape, a: int, head_dim: int, v_head_dim: int,
              group: int):
    """(``_head_lanes`` of head ``a`` in a block of q or k, the same in a
    block of v, o or dO): one mask where the two views are alike."""
    qk = _head_lanes(qk_shape, a, head_dim, group)
    if tuple(v_shape) == tuple(qk_shape) and v_head_dim == head_dim:
        return qk, qk
    return qk, _head_lanes(v_shape, a, v_head_dim, group)


def _only(x, lanes):
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _keep(row0, col0, shape, kv_axis: int, window=None):
    """Causal mask of a score block whose kv positions run along
    ``kv_axis`` (1: s = q kᵀ, 0: sᵀ = k qᵀ); with a ``window``, a query
    also loses the keys ``window`` or more positions behind it."""
    qpos = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - kv_axis)
    kpos = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, kv_axis)
    if window is None:
        return kpos <= qpos
    return (kpos <= qpos) & (qpos - kpos < window)


def _wide(x, width):
    """[n, LANES] with every lane alike -> [n, width]: whole copies of its
    vregs, no lane broadcast (a slice where a toy block is narrower)."""
    if width > LANES:
        x = jnp.tile(x, (1, -(-width // LANES)))
    return x if x.shape[1] == width else x[:, :width]


def _loop_blocks(bounds, masked, body):
    """Run ``body(j, masked=m)`` over the consecutive block ranges
    [bounds[i], bounds[i + 1]) with ``m = masked[i]``: the blocks the
    causal diagonal or a window's edge crosses apply the mask, wholly
    visible ones skip it, wholly masked ones are in no range.  ``body``
    returns nothing: every running sum lives in a scratch ref."""
    for lo, hi, m in zip(bounds, bounds[1:], masked):
        if isinstance(lo, int) and isinstance(hi, int) and lo == hi:
            continue  # no causal mask: the masked range is empty
        jax.lax.fori_loop(
            lo, hi, lambda j, carry, m=m: body(j, masked=m), None)


# how ``_loop_blocks`` masks the three ranges a walk is cut into: the
# kv blocks of a q block run (window's edge, wholly visible, diagonal),
# the q blocks of a kv block (diagonal, wholly visible, window's edge)
MASKED = (True, False, True)


def _kv_range(qi, block_q, block_k, nk, causal, window=None):
    """(first, first wholly visible, first on the diagonal, end) kv blocks
    of q block ``qi``: blocks before the first and from ``end`` on are
    wholly masked.  Without a window the first two are 0."""
    if not causal:
        return 0, 0, nk, nk
    if window is None:  # op for op what a causal kernel always lowered to
        return 0, 0, (qi * block_q + 1) // block_k, \
            jnp.minimum(nk, (qi * block_q + block_q - 1) // block_k + 1)
    q0 = qi * block_q
    diag = (q0 + 1) // block_k
    end = jnp.minimum(nk, (q0 + block_q - 1) // block_k + 1)
    # row q0 sees back to key q0 - window + 1; every row of the block
    # sees all of a kv block that starts at q0 + block_q - window or later
    first = jnp.maximum(q0 - window + 1, 0) // block_k
    whole = (jnp.maximum(q0 + block_q - window, 0) + block_k - 1) // block_k
    return first, jnp.clip(whole, first, diag), diag, end


def _q_range(ki, block_q, block_k, nq, causal, window=None):
    """(first visible, first wholly visible, first on the window's edge,
    end) q blocks of kv block ``ki``.  Without a window the last two are
    ``nq``."""
    if not causal:
        return 0, 0, nq, nq
    if window is None:
        return (ki * block_k) // block_q, jnp.minimum(
            nq, (ki * block_k + block_k + block_q - 2) // block_q), nq, nq
    k0 = ki * block_k
    first = k0 // block_q
    whole = jnp.minimum(nq, (k0 + block_k + block_q - 2) // block_q)
    # key k0 + block_k - 1 is seen up to row k0 + block_k + window - 2;
    # a q block is wholly inside the window of key k0 while it ends
    # before row k0 + window
    end = jnp.minimum(nq, (k0 + block_k + window - 2) // block_q + 1)
    whole = jnp.minimum(whole, end)
    return first, whole, jnp.clip((k0 + window) // block_q, whole, end), end


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, sm_scale: float,
                causal: bool, window, block_k: int, head_dim: int,
                v_head_dim: int, group: int, chosen: bool = False):
    # with a choice: its tiles of this q block [nk, bq, bk], the table [nq, nk]
    keep_ref, tiles_ref, *refs = refs if chosen else (None, None, *refs)
    # the running max and sum [bq, 128] (every lane alike) and the
    # accumulator [bq, Wv], float32 scratch a head block's head at a time
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    block_q, width = o_ref.shape
    qi, nk = pl.program_id(1), k_ref.shape[0] // block_k
    bounds = _kv_range(qi, block_q, block_k, nk, causal, window)
    if chosen:  # every block up to the diagonal's masks by its tile
        bounds = (0, 0, 0, bounds[3])
    q = q_ref[...]
    out = jnp.zeros(o_ref.shape, jnp.float32)
    for a in range(group):
        q_lanes, lanes = _lanes_of(q.shape, o_ref.shape, a, head_dim,
                                   v_head_dim, group)
        qa = _only(q, q_lanes)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def body(j, masked):
            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = _dot(qa, k, _NT) * sm_scale                  # [BQ, BK]
            if masked:
                seen = keep_ref[j] != 0 if chosen else _keep(
                    qi * block_q, j * block_k, s.shape, 1, window)
                s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _wide(m_new, s.shape[1]))
            alpha = jnp.exp(m_prev - m_new)                  # [BQ, 128]
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * _wide(alpha, width) + _dot(
                p.astype(v.dtype), v)

        if chosen:  # a block pair with no chosen pair is stepped over
            visit = body

            def body(j, masked):
                pl.when(tiles_ref[qi, j] != 0)(
                    functools.partial(visit, j, masked))

        _loop_blocks(bounds, MASKED, body)
        l = jnp.maximum(l_ref[...], 1e-30)
        o_a = acc_ref[...] / _wide(l, width)
        out = o_a if lanes is None else jnp.where(lanes, o_a, out)
        # log-sum-exp per query row: all the backward needs to recompute
        # p.  Stored as a lane-dense row.
        lse_ref[a:a + 1, :] = (m_ref[...] + jnp.log(l)).T[:1]
    o_ref[...] = out.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                sm_scale: float, causal: bool, window, block_q: int,
                head_dim: int, v_head_dim: int, group: int,
                chosen: bool = False):
    # with a choice: its transposed tiles of this kv block [nq, bk, bq], the
    # table [nq, nk]
    keep_ref, tiles_ref, *refs = refs if chosen else (None, None, *refs)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    block_k = k_ref.shape[0]
    ki, nq = pl.program_id(1), q_ref.shape[0] // block_q
    bounds = _q_range(ki, block_q, block_k, nq, causal, window)
    if chosen:  # every block from the diagonal's on masks by its tile
        bounds = (bounds[0], nq, nq, nq)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
    k, v = k_ref[...], v_ref[...]
    heads = []
    for a in range(group):
        k_lanes, v_lanes = _lanes_of(k.shape, v.shape, a, head_dim,
                                     v_head_dim, group)
        heads.append((_only(k, k_lanes), _only(v, v_lanes)))

    def body(i, masked):
        rows = pl.ds(i * block_q, block_q)
        q, do = q_ref[rows, :], do_ref[rows, :]
        if masked:
            keep = keep_ref[i] != 0 if chosen else _keep(
                i * block_q, ki * block_k, (block_k, block_q), 0, window)
        # every right-hand operand is zero off head a's lanes, so the
        # heads of a block add up in one accumulator a gradient
        for a, (ka, va) in enumerate(heads):
            q_lanes, do_lanes = _lanes_of(q.shape, do.shape, a, head_dim,
                                          v_head_dim, group)
            st = _dot(ka, q, _NT) * sm_scale                 # [BK, BQ]
            if masked:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[a, pl.ds(i, 1), :])
            dv_acc[...] += _dot(pt.astype(do.dtype), _only(do, do_lanes))
            dst = (pt * (_dot(va, do, _NT) - delta_ref[a, pl.ds(i, 1), :])
                   ).astype(q.dtype)
            dk_acc[...] += _dot(dst, _only(q, q_lanes))
            dq_acc[rows, :] += _dot(dst, ka, _TN)            # [BQ, W]

    if chosen:  # a block pair with no chosen pair is stepped over
        visit = body

        def body(i, masked):
            pl.when(tiles_ref[i, ki] != 0)(functools.partial(visit, i, masked))

    _loop_blocks(bounds, MASKED, body)
    dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


class _Plan:
    """Shapes, grids and block specs shared by the two kernels: the
    [L, H·D] view in column blocks of ``group`` heads; one side of the
    score block is a grid axis, the other is whole in VMEM and walked by
    the kernel's own loop."""

    def __init__(self, q, k, v, block_q, block_k, causal, interpret,
                 window=None):
        self.Lq, self.H, self.D = q.shape
        self.Lk, self.Dv = k.shape[0], v.shape[-1]
        if window is not None and not causal:
            raise ValueError("a window is defined under the causal mask only")
        self.bq, self.bk = min(block_q, self.Lq), min(block_k, self.Lk)
        if self.Lq % self.bq or self.Lk % self.bk:
            raise ValueError(
                f"sequence ({self.Lq},{self.Lk}) must divide blocks "
                f"({self.bq},{self.bk})"
            )
        # a shape head_group refuses runs as one block of every head:
        # right for the interpreter, not sent to a chip by the policy
        self.group = head_group(self.H, self.D, self.Dv) or self.H
        # columns of a block of q and k, and of v, o and dO
        self.W, self.Wv = self.group * self.D, self.group * self.Dv
        self.nh = self.H // self.group
        # q heads to a k/v head: a q head block reads the column block of
        # its k/v head, so no repeated k or v is made in HBM
        self.rep = self.H // k.shape[1]
        if self.rep * k.shape[1] != self.H or (
                self.rep > 1 and self.group > 1):
            raise ValueError(
                f"{self.H} q heads of {self.D} over {k.shape[1]} k/v heads: "
                "shared k/v heads need one head a column block")
        self.nq, self.nk = self.Lq // self.bq, self.Lk // self.bk
        self.interpret = interpret
        self.consts = dict(sm_scale=1.0 / (self.D ** 0.5), causal=causal,
                           window=window, head_dim=self.D,
                           v_head_dim=self.Dv, group=self.group)

    def column(self, kv):
        """q head block -> column block: its own, or its k/v head's."""
        rep = self.rep
        return (lambda h: h // rep) if kv and rep > 1 else (lambda h: h)

    def block(self, rows, kv=False, v=False):
        """``rows`` of the grid step's row block in the head block's columns:
        of q or k, or (``v``) of v, o or dO."""
        col = self.column(kv)
        return pl.BlockSpec((rows, self.Wv if v else self.W),
                            lambda h, i: (i, col(h)))

    def whole(self, rows, kv=False, v=False, **kw):
        col = self.column(kv)
        return pl.BlockSpec((rows, self.Wv if v else self.W),
                            lambda h, i: (0, col(h)), **kw)

    def call(self, name, kernel, grid, in_specs, out_specs, out_shape,
             sequential=False, scratch_shapes=(), **consts):
        """``name`` is the custom call's in a device trace.  The second
        grid axis runs in order on one core where ``sequential``: a
        scratch buffer then carries from one of its steps to the next."""
        kwargs = {}
        if not self.interpret:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "arbitrary" if sequential else "parallel"),
                vmem_limit_bytes=VMEM_LIMIT,
            )
        return pl.pallas_call(
            functools.partial(kernel, **self.consts, **consts), grid=grid,
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch_shapes, interpret=self.interpret,
            name=name, **kwargs,
        )

    def flat(self, t):
        return t.reshape(t.shape[0], t.shape[1] * t.shape[2])

    def choice(self, keep, tiles, transposed: bool):
        """(operands, block specs, the kernels' constant) of a choice, or
        three empty ones without: ``keep`` [Lq, Lk] by tiles, those of a grid
        step's row block (of its kv block, transposed, for the backward) on a
        leading dim; the table whole in SMEM."""
        if keep is None:
            return (), [], {}
        if tiles.shape != (self.nq, self.nk):
            raise ValueError(f"a tile table of {tiles.shape} for "
                             f"{(self.nq, self.nk)} blocks")
        by_tile = keep.reshape(self.nq, self.bq, self.nk, self.bk)
        if transposed:
            spec = pl.BlockSpec((None, self.nq, self.bk, self.bq),
                                lambda h, j: (j, 0, 0, 0))
            by_tile = by_tile.transpose(2, 0, 3, 1)
        else:
            spec = pl.BlockSpec((None, self.nk, self.bq, self.bk),
                                lambda h, i: (i, 0, 0, 0))
            by_tile = by_tile.transpose(0, 2, 1, 3)
        table = pl.BlockSpec(memory_space=pltpu.SMEM)
        return (by_tile, tiles), [spec, table], {"chosen": True}


def _flash_heads_impl(q, k, v, causal, block_q, block_k, interpret,
                      window=None, keep=None, tiles=None):
    """(o [L, H, Dv], lse [H, L]) of q, k [L, H, D] and v [L, H, Dv]."""
    pn = _Plan(q, k, v, block_q, block_k, causal, interpret, window)
    choice, choice_specs, chosen = pn.choice(keep, tiles, transposed=False)
    out, lse = pn.call(
        "flash_fwd", _fwd_kernel, (pn.nh, pn.nq),
        [pn.block(pn.bq), pn.whole(pn.Lk, kv=True),
         pn.whole(pn.Lk, kv=True, v=True), *choice_specs],
        [pn.block(pn.bq, v=True),
         pl.BlockSpec((None, pn.group, pn.bq), lambda h, i: (h, 0, i))],
        [jax.ShapeDtypeStruct((pn.Lq, pn.H * pn.Dv), q.dtype),
         jax.ShapeDtypeStruct((pn.nh, pn.group, pn.Lq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in (
            (pn.bq, LANES), (pn.bq, LANES), (pn.bq, pn.Wv))],
        block_k=pn.bk, **chosen,
    )(pn.flat(q), pn.flat(k), pn.flat(v), *choice)
    return out.reshape(pn.Lq, pn.H, pn.Dv), lse.reshape(pn.H, pn.Lq)


def _flash_bwd_impl(q, k, v, o, lse, do, dlse, causal, block_q, block_k,
                    interpret, window=None, keep=None, tiles=None):
    """Exact flash backward as one kernel.  Standard formulas:

        p_ij  = exp(s_ij - lse_i)
        dv_j  = pᵀ dO           dp_ij = dO_i · v_j
        ds_ij = p_ij (dp_ij - delta_i),  delta_i = dO_i · O_i - dlse_i
        dq_i  = scale · Σ_j ds_ij k_j
        dk_j  = scale · Σ_i ds_ij q_i

    ``dlse`` is the cotangent of the lse OUTPUT (nonzero when the caller
    uses lse, e.g. the ring merge weights): d lse_i / d s_ij = p_ij.

    ``dk`` and ``dv`` of a k/v head that several q heads share leave the
    kernel a q head and are summed over the group here.
    """
    pn = _Plan(q, k, v, block_q, block_k, causal, interpret, window)
    choice, choice_specs, chosen = pn.choice(keep, tiles, transposed=True)
    delta = (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(-1).T \
        - dlse.astype(jnp.float32)                           # [H, Lq]
    ops = (pn.flat(q), pn.flat(k), pn.flat(v), pn.flat(do))
    flat_q = jax.ShapeDtypeStruct((pn.Lq, pn.H * pn.D), q.dtype)
    flat_k = jax.ShapeDtypeStruct((pn.Lk, pn.H * pn.D), k.dtype)  # a q head
    flat_v = jax.ShapeDtypeStruct((pn.Lk, pn.H * pn.Dv), v.dtype)

    # [H, L] statistics by head block, a q block's row on a leading dim
    # for the kernel's loop to pick
    rows = pl.BlockSpec((None, pn.group, pn.nq, pn.bq),
                        lambda h, j: (h, 0, 0, 0))
    # whole in VMEM: q, dO and the dq block in two buffers each, and the
    # float32 accumulator.  Where that sum would leave the score blocks
    # no room under VMEM_LIMIT, q and dO get one buffer: their block
    # changes only with the head block, and a long sequence hides the
    # wait (at the cells' L = 1024 it cost 0.17 ms in 1.22: PERF.md §6)
    resident = pn.Lq * (pn.W * (4 * q.dtype.itemsize + 4)
                        + pn.Wv * 2 * q.dtype.itemsize)
    whole = pn.whole if resident <= VMEM_LIMIT * 3 // 4 else \
        functools.partial(pn.whole, pipeline_mode=pl.Buffered(1))
    dq, dk, dv = pn.call(
        "flash_bwd", _bwd_kernel, (pn.nh, pn.nk),
        [whole(pn.Lq), pn.block(pn.bk, kv=True),
         pn.block(pn.bk, kv=True, v=True), whole(pn.Lq, v=True), rows, rows,
         *choice_specs],
        [pn.whole(pn.Lq), pn.block(pn.bk), pn.block(pn.bk, v=True)],
        [flat_q, flat_k, flat_v], sequential=True,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in (
            (pn.Lq, pn.W), (pn.bk, pn.W), (pn.bk, pn.Wv))],
        block_q=pn.bq, **chosen,
    )(*ops, *(t.reshape(pn.nh, pn.group, pn.nq, pn.bq)
              for t in (lse, delta)), *choice)
    if pn.rep > 1:
        dk, dv = (t.reshape(pn.Lk, pn.H // pn.rep, pn.rep, -1).astype(
            jnp.float32).sum(axis=2).astype(k.dtype) for t in (dk, dv))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, causal, block_q, block_k, interpret,
                             window=None):
    """Differentiable (o, lse) pair — the ring path consumes BOTH (the
    merge weights are lse functions), so the backward carries the lse
    cotangent too (one extra ``p * dlse`` term in ds)."""
    return _flash_heads_impl(q, k, v, causal, block_q, block_k, interpret,
                             window)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_heads_impl(q, k, v, causal, block_q, block_k, interpret,
                                 window)
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    return _flash_bwd_impl(q, k, v, out, lse, do, dlse, causal, block_q,
                           block_k, interpret, window)


flash_attention_with_lse.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_chosen(q, k, v, keep, tiles, block, interpret):
    """``o`` of causal attention over the pairs ``keep`` holds 1 for: the
    softmax over a query's chosen keys.  ``keep`` and ``tiles`` are data and
    get no gradient; q, k and v get that of the softmax over the set held
    fixed."""
    return _chosen_fwd(q, k, v, keep, tiles, block, interpret)[0]


def _chosen_fwd(q, k, v, keep, tiles, block, interpret):
    out, lse = _flash_heads_impl(q, k, v, True, block, block, interpret,
                                 keep=keep, tiles=tiles)
    return out, (q, k, v, keep, tiles, out, lse)


def _chosen_bwd(block, interpret, res, do):
    q, k, v, keep, tiles, out, lse = res
    return (*_flash_bwd_impl(q, k, v, out, lse, do, jnp.zeros_like(lse), True,
                             block, block, interpret, keep=keep, tiles=tiles),
            None, None)


_flash_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,
    keep: jax.Array | None = None,
    tiles: jax.Array | None = None,
) -> jax.Array:
    """Flash attention over [L, H, D] (no batch; vmap for batches).

    ``v`` may have a head size of its own, [L, H, Dv]: ``o`` then has it too
    (scores are scaled by ``1 / sqrt(D)`` of q and k).

    ``window`` (causal only): a query sees the ``window`` latest keys,
    itself included; block pairs wholly behind it are skipped like those
    above the diagonal.  ``k`` and ``v`` may hold fewer heads than ``q``
    (head size a multiple of 128): k/v head ``g`` serves q heads
    ``g * rep .. (g + 1) * rep - 1``.

    ``keep`` [L, L] int8 with ``tiles`` [L / block, L / block]
    (``ops/sparse_select.select_topk``, at ``block_q = block_k = block``): a
    choice of keys a query, under the causal mask; a query sees the keys its
    row holds 1 for, and a block pair the table marks empty is stepped over.

    Drop-in for ``parallel.ring_attention.blockwise_attention`` where
    shapes divide the block sizes.  DIFFERENTIABLE: the custom backward
    recomputes p per KV block from the kernel's saved log-sum-exp — an
    exact O(L)-memory gradient, so the training path never materializes
    [L, L] (tests/test_flash_attention.py pins grads against dense
    attention).
    """
    if keep is not None:
        if tiles is None or not causal or window is not None \
                or block_q != block_k:
            raise ValueError("a choice of keys comes with its tile table, "
                             "under the causal mask, with no window and "
                             "square blocks")
        return _flash_chosen(q, k, v, keep, tiles, block_q, interpret)
    if window is not None and window >= k.shape[0]:
        window = None  # every key a causal query sees is inside it
    out, _ = flash_attention_with_lse(
        q, k, v, causal, block_q, block_k, interpret, window
    )
    return out


def flash_attn_fn(block_q: int = 128, block_k: int = 128,
                  interpret: bool = False):
    """Adapter matching the TransformerLM ``attn_fn`` signature."""

    def attn(q, k, v, causal, window=None, keep=None, tiles=None):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               window=window, keep=keep, tiles=tiles)

    return attn


# longest side the kernels hold whole in VMEM beside the score blocks under
# VMEM_LIMIT, in float32 at 128 lanes (16 MiB an array).  Forward: k and v
# in two buffers each, 64 MiB.  Backward: q and dO in one buffer each, the
# dq block in two, its accumulator: 80 MiB (112 with q and dO in two)
MAX_LENGTH = 32768


def pick_block(length: int, head_dim: int = 128) -> int:
    """Block size (q and kv alike, forward and backward) for a sequence
    of ``length`` at ``head_dim``: the largest of 512, 256, 128 that
    divides it; 0 if none does or the sequence is longer than MAX_LENGTH
    (the caller falls back to the lax blockwise path).

    Measured on one v5e chip (PERF.md §6, PR 30; bf16 [8, 1024, 20, 64],
    the kernels' device time in a trace, ms a layer, forward + backward):
    512-blocks 0.82 + 0.95, 1024 (no causal block skipped) 0.75 + 1.16,
    256 1.11 + 1.15; PR 26 had 128 at 6.89 for both against 2.93 in
    512-blocks then, and 8.01 for the lax blockwise scan.  A block pair
    costs a fixed ~400 cycles beside its elementwise passes, so small
    blocks lose more than their finer causal skip wins.  The forward
    alone would take 1024; one size serves both kernels.  Both head sizes
    the kernels take on a chip (``head_group``) run 128-lane blocks, so
    the choice does not depend on ``head_dim`` today.
    """
    del head_dim
    if length > MAX_LENGTH:
        return 0
    return next((b for b in (512, 256, 128) if length % b == 0), 0)
