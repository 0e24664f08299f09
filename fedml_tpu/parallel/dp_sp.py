"""DP×SP federated rounds: long-context clients on a (clients, sp) mesh.

Composes the two first-class axes of this framework: the FedAvg clients
axis (one FL client per mesh row, masked weighted psum aggregation —
``parallel/spmd.py``) and sequence parallelism (each client's token
sequences sharded over the ``sp`` axis with ring attention —
``parallel/ring_attention.py``).  The result is federated fine-tuning
over sequences LONGER than one chip's attention memory: every client's
local update runs as an sp-way SPMD program, and the cross-client
aggregation rides the same compiled round.  The reference has no
analogue on either axis (SURVEY.md §2.6, §5.7).

Correctness structure (all inside ONE shard_map over both axes):

- model params are REPLICATED over ``sp``; each shard computes the
  gradient through its own token shard, so a cross-shard combine is
  inserted as an optax transform ahead of the client optimizer
  (``pmean_gradients`` — MEAN, because the psum-transpose identity
  already scales each shard's cotangent by the axis size), which keeps
  the replicas bit-identical after every step.
- the loss is globally normalized: per-shard masked sums are psum'd
  over ``sp`` before the division (``make_sp_loss_fn``), so token counts
  on other shards weigh the local gradient correctly.
- causal positions are global: the transformer's ``pos_offset_fn`` adds
  ``axis_index(sp) * L_local``, and attention is the exact ring
  (lax blockwise or the pallas flash ring).
- aggregation across clients is ``make_round_fn``'s masked weighted
  psum with ``axis_name="clients"`` — unchanged.

Parity is pinned against a single-device oracle running the same round
on the full-length model (``tests/test_dp_sp.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
from fedml_tpu.core.client import make_local_update
from fedml_tpu.core.losses import LossFn, masked_softmax_ce
from fedml_tpu.models.base import ModelBundle
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.parallel.mesh import device_grid

PyTree = Any


def make_dp_sp_mesh(
    n_clients_axis: int, n_sp: int, *, devices=None
) -> Mesh:
    return Mesh(device_grid((n_clients_axis, n_sp), devices),
                axis_names=("clients", "sp"))


def pmean_gradients(axis: str) -> optax.GradientTransformation:
    """Combine replicated-parameter gradients across ``axis`` BEFORE the
    optimizer.  Each shard's AD only covers its own token shard's paths
    through the shared params, so a cross-shard combine is required to
    keep the replicas identical — and it must be pMEAN, not psum:
    JAX transposes ``lax.psum`` to ``lax.psum``, so differentiating the
    globally-psum'd loss already hands every shard an axis-size-scaled
    cotangent (the classic psum-gradient identity), and the mean exactly
    cancels that factor.  Pinned against the single-device oracle in
    tests/test_dp_sp.py — a psum here was measured as a uniform
    axis_size× gradient inflation."""

    def update(grads, state, params=None):
        del params
        return jax.tree_util.tree_map(
            lambda g: lax.pmean(g, axis), grads
        ), state

    return optax.GradientTransformation(lambda _: (), update)


def make_sp_loss_fn(axis: str, base: LossFn = masked_softmax_ce) -> LossFn:
    """Globally-normalized loss over a sequence-sharded batch: psum the
    masked sums over ``axis``, divide once — so every shard's local
    gradient carries the correct global weight, and the metrics each
    shard reports are already the full-sequence totals."""

    def loss_fn(logits, y, mask):
        _, aux = base(logits, y, mask)
        s = lax.psum(aux["loss_sum"], axis)
        c = lax.psum(aux["count"], axis)
        corr = lax.psum(aux["correct"], axis)
        loss = s / jnp.maximum(c, 1.0)
        return loss, {"loss_sum": s, "correct": corr, "count": c}

    return loss_fn


def sp_transformer_bundle(
    *,
    vocab_size: int,
    embed_dim: int,
    num_heads: int,
    num_layers: int,
    max_len: int,
    axis: str = "sp",
    attn_impl: str = "lax",
    block_size: int = 512,
    flash_block: Optional[int] = None,
    flash_interpret: bool = False,
) -> ModelBundle:
    """TransformerLM whose attention is the ring over ``axis`` and whose
    positions are shard-global — valid ONLY inside shard_map."""
    from fedml_tpu.parallel.ring_attention import (
        ring_attention,
        ring_flash_attention,
    )

    if attn_impl not in ("lax", "flash"):
        raise ValueError(f"attn_impl must be 'lax' or 'flash', got {attn_impl!r}")
    if attn_impl == "flash" and block_size != 512:
        # same guard as sequence_parallel_lm: block_size tunes the LAX
        # ring's KV chunking; the flash path's pallas block is
        # flash_block — reject the silent-ignore trap at the shared layer
        raise ValueError(
            "block_size applies to attn_impl='lax' only; tune the flash "
            "path with flash_block"
        )
    attn_fn = (
        (lambda q, k, v, causal: ring_flash_attention(
            q, k, v, axis, causal=causal, block=flash_block,
            interpret=flash_interpret))
        if attn_impl == "flash"
        else (lambda q, k, v, causal: ring_attention(
            q, k, v, axis, causal=causal, block_size=block_size))
    )
    module = TransformerLM(
        vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
        num_layers=num_layers, max_len=max_len, attn_fn=attn_fn,
        pos_offset_fn=lambda L: lax.axis_index(axis) * L,
    )
    # input_shape is the LOCAL token shard; init must happen OUTSIDE the
    # mesh with the plain reference module (sequence.py convention)
    return ModelBundle(module=module, input_shape=(max_len,),
                       input_dtype=jnp.int32)


def make_dp_sp_round_fn(
    mesh: Mesh,
    *,
    vocab_size: int,
    embed_dim: int,
    num_heads: int,
    num_layers: int,
    max_len: int,
    optimizer: optax.GradientTransformation,
    epochs: int = 1,
    compute_dtype=None,
    attn_impl: str = "lax",
    block_size: int = 512,
    flash_block: Optional[int] = None,
    flash_interpret: bool = False,
    donate: bool = True,
):
    """Build the DP×SP FedAvg round.

    round_fn(state, x, y, mask, num_samples, participation, slot_ids)
    with x/y [C, steps, B, L] (L divisible by the sp axis), mask
    [C, steps, B] per-sequence.  Returns (round_fn, shard_data,
    init_fn): ``init_fn(rng)`` initializes params with the plain
    full-length module (identical tree), ``shard_data`` lays the packed
    block out on the mesh (sequence dim over ``sp``).
    """
    bundle = sp_transformer_bundle(
        vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
        num_layers=num_layers, max_len=max_len, attn_impl=attn_impl,
        block_size=block_size, flash_block=flash_block,
        flash_interpret=flash_interpret,
    )
    # gradient pmean over sp BEFORE the client optimizer (see
    # pmean_gradients for why mean, not sum)
    opt = optax.chain(pmean_gradients("sp"), optimizer)
    local_update = make_local_update(
        bundle, opt, epochs, make_sp_loss_fn("sp"),
        compute_dtype=compute_dtype,
    )
    inner = make_round_fn(local_update, axis_name="clients")

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(),                          # state replicated
            P("clients", None, None, "sp"),   # x tokens
            P("clients", None, None, "sp"),   # y targets
            P("clients"),                 # per-sequence mask
            P("clients"),                 # num_samples
            P("clients"),                 # participation
            P("clients"),                 # slot ids
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def dp_sp_round(state, x, y, mask, num_samples, participation, slot_ids):
        return inner(state, x, y, mask, num_samples, participation, slot_ids)

    def init_fn(rng: jax.Array) -> PyTree:
        ref = TransformerLM(
            vocab_size=vocab_size, embed_dim=embed_dim,
            num_heads=num_heads, num_layers=num_layers, max_len=max_len,
        )
        dummy = jnp.zeros((1, max_len), jnp.int32)
        return ref.init({"params": rng}, dummy, train=False)

    def shard_data(arrays):
        x, y, mask, num_samples, participation, slot_ids = arrays
        cl = NamedSharding(mesh, P("clients"))
        seq = NamedSharding(mesh, P("clients", None, None, "sp"))
        return (
            jax.device_put(jnp.asarray(x), seq),
            jax.device_put(jnp.asarray(y), seq),
            jax.device_put(jnp.asarray(mask), cl),
            jax.device_put(jnp.asarray(num_samples), cl),
            jax.device_put(jnp.asarray(participation), cl),
            jax.device_put(jnp.asarray(slot_ids), cl),
        )

    round_fn = jax.jit(dp_sp_round, donate_argnums=(0,) if donate else ())
    return round_fn, shard_data, init_fn
