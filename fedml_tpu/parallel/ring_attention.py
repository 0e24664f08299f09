"""Ring attention — sequence/context parallelism over a device mesh.

The reference has no attention at all (SURVEY.md §5.7: 2-layer LSTMs,
80-char windows); this module is the TPU-native long-context substrate
the rebuild adds so the mesh design scales past it.  Design follows the
public ring-attention recipe (Liu et al. 2023, blockwise online-softmax
attention with K/V blocks rotating around the ICI ring):

- ``blockwise_attention``: single-device chunked attention with online
  softmax — O(seq) memory, exact (not approximate).
- ``ring_attention``: inside ``shard_map`` over a sequence-sharded axis,
  each device holds one Q/K/V shard; after attending its local block,
  K/V shards rotate via ``lax.ppermute`` (ICI neighbor exchange) for
  ``axis_size - 1`` steps while local attention accumulates (m, l, o)
  online-softmax state.  Compute overlaps communication since each
  step's matmuls and the permute are independent XLA ops the scheduler
  pipelines.
- causal masking uses GLOBAL positions (shard offset = axis index), so
  the sharded result equals dense causal attention up to float addition
  order.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """One (q-block, kv-block) attention contribution.

    q [Lq, H, D], k/v [Lk, H, D], bias [Lq, Lk] additive (0 / -inf mask).
    Returns (m [Lq,H], l [Lq,H], o [Lq,H,D]) online-softmax partials.
    """
    d = q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    s = s + bias[None, :, :]
    m = s.max(axis=-1)                      # [H, Lq]
    p = jnp.exp(s - m[..., None])           # [H, Lq, Lk]
    l = p.sum(axis=-1)                      # [H, Lq]
    o = jnp.einsum("hqk,khd->qhd", p, v)    # [Lq, H, D]
    return m.swapaxes(0, 1), l.swapaxes(0, 1), o


def _merge(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partial states."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return m, l, o


def _partial_attention(q, k, v, *, causal, block_size, q_offset, kv_offset,
                       window=None, q_copies=1, keep=None):
    """(m, l, o) partials of Q [Lq,H,D] against K/V [Lk,H,D], scanned in
    KV blocks.  Pads ragged K/V to a block multiple and masks the pad —
    the ONE shared inner loop for both the single-device and ring paths.

    ``q_offset``/``kv_offset`` are GLOBAL positions of the first
    query/key; kv_offset may be a traced value (ring path).  ``window``
    (causal only) also hides keys ``window`` or more positions behind the
    query.  ``q_copies`` says the rows of ``q`` are that many runs of the
    same positions, one after another (the q heads that share a k/v head,
    folded into rows by ``blockwise_attention``).  ``keep`` [positions of q,
    Lk] (int8 or bool) also hides every pair it holds 0 for, and a kv
    block's scores and probabilities are then computed again in the backward
    and not saved: a query with a choice sees keys all along its row, so the
    saved blocks would be all of [H, Lq, Lk].
    """
    if window is not None and not causal:
        raise ValueError("a window is defined under the causal mask only")
    Lq, H, D = q.shape
    Lk = k.shape[0]
    bs = min(block_size, Lk)
    n_blocks = (Lk + bs - 1) // bs
    pad = n_blocks * bs - Lk
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
        if keep is not None:
            keep = jnp.pad(keep, ((0, 0), (0, pad)))

    qpos = q_offset + jnp.arange(Lq)
    if q_copies > 1:
        qpos = q_offset + jnp.tile(jnp.arange(Lq // q_copies), q_copies)

    def body(carry, i):
        m, l, o = carry
        kb = lax.dynamic_slice_in_dim(k, i * bs, bs)
        vb = lax.dynamic_slice_in_dim(v, i * bs, bs)
        # local (unshifted) key index for pad masking; global for causal
        local_kpos = i * bs + jnp.arange(bs)
        bias = jnp.where(local_kpos[None, :] < Lk, 0.0, NEG_INF)
        if causal:
            kpos = kv_offset + local_kpos
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen &= qpos[:, None] - kpos[None, :] < window
            bias = bias + jnp.where(seen, 0.0, NEG_INF)
        else:
            bias = jnp.broadcast_to(bias, (Lq, bs))
        if keep is not None:
            chosen = lax.dynamic_slice_in_dim(keep, i * bs, bs, axis=1) != 0
            bias = bias + jnp.where(jnp.tile(chosen, (q_copies, 1)), 0.0,
                                    NEG_INF)
        mb, lb, ob = _block_attn(q, kb, vb, bias.astype(q.dtype))
        return _merge(m, l, o, mb, lb, ob), None

    # derive carry inits from q so they inherit q's varying-manual-axes
    # type under shard_map (JAX ≥0.9 typed vma; a fresh jnp.full would
    # be unvarying and fail lax.scan's carry typecheck on the ring path)
    zero = jnp.zeros_like(q[:, :, 0])       # [Lq, H]
    m0 = zero + jnp.asarray(NEG_INF, q.dtype)
    l0 = zero
    o0 = jnp.zeros_like(q) if v.shape[-1] == D else jnp.broadcast_to(
        zero[..., None], (Lq, H, v.shape[-1]))  # v heads of another size
    (m, l, o), _ = lax.scan(body if keep is None else jax.checkpoint(body),
                            (m0, l0, o0), jnp.arange(n_blocks))
    return m, l, o


def _normalize(m, l, o):
    del m
    return o / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = False,
    block_size: int = 512,
    q_offset: int = 0,
    kv_offset: int = 0,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention over [L, H, D] tensors in KV blocks (O(L) memory).

    ``q_offset``/``kv_offset`` are the global positions of the first
    query/key — how ring shards express causal masks.  ``window``: a causal
    query sees only the ``window`` latest keys, itself included.  ``k`` and
    ``v`` may hold fewer heads than ``q``: k/v head ``g`` serves q heads
    ``g * rep .. (g + 1) * rep - 1``, which are folded into rows so that no
    repeated ``k`` or ``v`` is made.  ``v``'s head size may differ from
    ``q``'s and ``k``'s: the output has ``v``'s.  ``keep`` [Lq, Lk] (int8 or
    bool; ``ops/sparse_select.py``): a pair it holds 0 for is hidden, whatever
    ``causal`` and ``window`` say, for every head alike, and a kv block's
    probabilities are computed again in the backward, not saved.
    """
    Lq, H, D = q.shape
    rep = H // k.shape[1]
    if rep * k.shape[1] != H:
        raise ValueError(f"{H} q heads over {k.shape[1]} k/v heads")
    if rep > 1:
        q = q.reshape(Lq, H // rep, rep, D).transpose(2, 0, 1, 3).reshape(
            rep * Lq, H // rep, D)
    out = _normalize(*_partial_attention(
        q, k, v, causal=causal, block_size=block_size,
        q_offset=q_offset, kv_offset=kv_offset, window=window, q_copies=rep,
        keep=keep,
    ))
    if rep > 1:
        out = out.reshape(rep, Lq, H // rep, -1).transpose(1, 2, 0, 3).reshape(
            Lq, H, -1)
    return out


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    block_size: int = 512,
) -> jax.Array:
    """Sequence-parallel exact attention INSIDE shard_map.

    Each device holds the local shard [L_local, H, D] of a sequence
    sharded over ``axis_name``.  The local K/V block is attended first;
    then K/V rotate left around the ring for ``axis_size - 1`` steps so
    every query attends every key with no wasted final exchange.
    Returns the local output shard.
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    L = q.shape[0]
    q_offset = my_idx * L

    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]

    # step 0: the resident (local) K/V shard
    state = _partial_attention(
        q, k, v, causal=causal, block_size=block_size,
        q_offset=q_offset, kv_offset=my_idx * L,
    )

    def step(carry, i):
        m, l, o, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        # after i rotations the resident shard started at device my+i
        src = (my_idx + i) % axis_size
        mb, lb, ob = _partial_attention(
            q, kc, vc, causal=causal, block_size=block_size,
            q_offset=q_offset, kv_offset=src * L,
        )
        m, l, o = _merge(m, l, o, mb, lb, ob)
        return (m, l, o, kc, vc), None

    (m, l, o, _, _), _ = lax.scan(
        step, (*state, k, v), jnp.arange(1, axis_size)
    )
    return _normalize(m, l, o)


def ring_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention whose per-step local attention is the pallas flash
    kernel (``ops/flash_attention.py``) instead of the lax blockwise scan
    — same rotation schedule and exact math, ~2x the per-step attention
    rate at long shard lengths on TPU.

    The cross-shard structure removes the need for global positions
    inside the kernel: under causal masking a source shard from an
    EARLIER ring rank is fully visible to every local query (non-causal
    step), a LATER rank contributes nothing (its lse is forced to -inf
    before the merge, costing one wasted kernel run the SPMD lockstep
    requires anyway — exactly like the lax path's fully-masked steps),
    and only the resident step is causal.  Per-source normalized outputs
    merge by log-sum-exp weights:

        m = max(lse_a, lse_b);  w_s = exp(lse_s - m)
        o = (w_a o_a + w_b o_b) / (w_a + w_b);  lse = m + log(w_a + w_b)

    Equivalence with the lax ring and dense attention is pinned in
    interpret mode (``tests/test_ring_attention.py``); default ``block``
    is ``pick_block`` of the shard length.
    """
    from fedml_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        pick_block,
    )

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    L = q.shape[0]
    b = block or pick_block(L, q.shape[-1])
    if not b:
        raise ValueError(
            f"shard length {L} has no block the flash kernels take "
            "(pick_block); use the lax ring_attention"
        )

    def flash(qq, kk, vv, c):
        # the custom_vjp pair: differentiable through BOTH o and lse
        # (the merge weights below are lse functions)
        o, lse = flash_attention_with_lse(qq, kk, vv, c, b, b, interpret)
        return o.astype(jnp.float32), lse  # o [L, H, D], lse [H, L]

    # step 0: the resident shard (the only causal step)
    o, lse = flash(q, k, v, causal)

    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        o, lse, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        src = (my_idx + i) % axis_size
        o_s, lse_s = flash(q, kc, vc, False)
        if causal:
            # later ranks' keys are all in this query shard's future
            lse_s = jnp.where(src < my_idx, lse_s, NEG_INF)
        m = jnp.maximum(lse, lse_s)
        wa = jnp.exp(lse - m)                       # [H, L]
        wb = jnp.exp(lse_s - m)
        den = jnp.maximum(wa + wb, 1e-30)
        waT = (wa / den).T[:, :, None]              # [L, H, 1]
        wbT = (wb / den).T[:, :, None]
        o = waT * o + wbT * o_s
        lse = m + jnp.log(den)
        return (o, lse, kc, vc), None

    (o, lse, _, _), _ = lax.scan(
        step, (o, lse, k, v), jnp.arange(1, axis_size)
    )
    return o.astype(q.dtype)


def dense_attention(q, k, v, *, causal: bool = False) -> jax.Array:
    """Reference implementation for tests: plain softmax(QKᵀ)V, [L, H, D]."""
    d = q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    if causal:
        L, Lk = q.shape[0], k.shape[0]
        mask = jnp.tril(jnp.ones((L, Lk), bool))
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)
