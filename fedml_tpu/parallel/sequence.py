"""Sequence parallelism: a transformer forward sharded over a mesh axis.

Composes ``shard_map`` + ``ring_attention`` so one logical sequence is
split across devices on the ICI ring: activations and KV blocks live
sharded, attention rotates K/V with ``ppermute``, and parameters stay
replicated.  Positions are globalized per shard, so the sharded forward
equals the single-device forward exactly.

This is the long-context capability the reference lacks entirely
(SURVEY.md §2.6 "TP/PP/SP/... absent") and the mesh axis the rest of
the framework reserves for it.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.parallel.ring_attention import (ring_attention,
                                               ring_flash_attention)

PyTree = Any


def make_sequence_mesh(n_devices: Optional[int] = None,
                       axis: str = "sp") -> Mesh:
    from fedml_tpu.parallel.spmd import make_1d_mesh

    return make_1d_mesh(n_devices, axis)


def sequence_parallel_lm(
    mesh: Mesh,
    *,
    vocab_size: int = 256,
    embed_dim: int = 128,
    num_heads: int = 4,
    num_layers: int = 2,
    max_len: int = 2048,
    block_size: int = 512,
    axis: str = "sp",
    attn_impl: str = "lax",
    flash_block: Optional[int] = None,
    flash_interpret: bool = False,
    remat: bool = False,
):
    """Build (module, init, apply) where ``apply(variables, tokens)``
    runs the forward with the sequence dim sharded over ``axis``.

    tokens: [B, L] with L divisible by the axis size.  Returns logits
    [B, L, V] (reassembled from shards by shard_map's out_spec).
    """
    if attn_impl not in ("lax", "flash"):
        raise ValueError(
            f"attn_impl must be 'lax' or 'flash', got {attn_impl!r}"
        )
    if attn_impl == "flash" and block_size != 512:
        # block_size tunes the LAX ring's KV chunking; the flash path's
        # pallas block is flash_block (pick_block default).  Reject the
        # silent-ignore trap instead of guessing which one was meant.
        raise ValueError(
            "block_size applies to attn_impl='lax' only; tune the flash "
            "path with flash_block"
        )
    module = TransformerLM(
        vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
        num_layers=num_layers, max_len=max_len, remat=remat,
        # "flash": the pallas-kernel ring path (ring_flash_attention) —
        # ~2x per-step attention at long shard lengths on TPU pods;
        # "lax" (default) is the portable blockwise ring.  flash_block
        # overrides pick_block; flash_interpret runs the kernel's CPU
        # interpreter (tests on the faked mesh).
        attn_fn=(
            (lambda q, k, v, causal: ring_flash_attention(
                q, k, v, axis, causal=causal, block=flash_block,
                interpret=flash_interpret))
            if attn_impl == "flash"
            else (lambda q, k, v, causal: ring_attention(
                q, k, v, axis, causal=causal, block_size=block_size))
        ),
        pos_offset_fn=lambda L: lax.axis_index(axis) * L,
    )

    def init(rng: jax.Array, sample_len: int = 128) -> PyTree:
        """Initialize OUTSIDE the mesh with plain blockwise attention —
        shapes/params are identical, only the attention impl differs."""
        ref = TransformerLM(
            vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
            num_layers=num_layers, max_len=max_len,
        )
        dummy = jnp.zeros((1, sample_len), jnp.int32)
        return ref.init({"params": rng}, dummy, train=False)

    def _local_forward(variables, tokens):
        return module.apply(variables, tokens, train=False)

    # check_vma only off for the flash path: pallas_call carries no vma
    # metadata on its out_shape under JAX 0.9's typed varying axes.  The
    # lax ring KEEPS the check — its carry inits were explicitly written
    # to satisfy vma typing (ring_attention.py), and the trace-time type
    # error is the guard against regressing that.
    sharded = shard_map(
        _local_forward, mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis, None),
        check_vma=(attn_impl != "flash"),
    )

    def apply(variables, tokens):
        # static-shape check: raises at trace time, before any clamped
        # positional-table gather could silently degrade output
        if tokens.shape[1] > max_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds max_len "
                f"{max_len}: positional table would clamp silently"
            )
        return sharded(variables, tokens)

    return module, init, jax.jit(apply)
