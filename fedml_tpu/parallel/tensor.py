"""Tensor parallelism: Megatron-style sharding of the transformer MLP
and attention projections over a ``tp`` mesh axis.

The reference has no tensor parallelism at all (SURVEY.md §2.6 —
TP/PP/SP "absent"); the rebuild's mesh reserves a model axis for it.
This module implements TP the idiomatic XLA way: instead of hand-writing
collectives, we annotate PARAMETER shardings (column-parallel up
projections, row-parallel down projections) with ``NamedSharding`` and
let the GSPMD partitioner insert the all-reduces — the "pick a mesh,
annotate shardings, let XLA insert collectives" recipe.

Sharding plan per transformer block (embed dim E, heads H):

- attention qkv projection kernel  [E, 3E]  → P(None, tp)   (column)
- attention output kernel          [E, E]   → P(tp, None)   (row; psum)
- MLP up kernel                    [E, 4E]  → P(None, tp)   (column)
- MLP up bias                      [4E]     → P(tp)
- MLP down kernel                  [4E, E]  → P(tp, None)   (row; psum)
- embeddings / LayerNorms / small biases    → replicated

Composable with the ``clients`` axis: a mesh of shape
(clients, tp) runs FL rounds where each client's forward/backward is
itself tensor-sharded.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.models.transformer import lax_attention, transformer_lm

PyTree = Any


def make_tp_mesh(n_devices: Optional[int] = None, axis: str = "tp") -> Mesh:
    from fedml_tpu.parallel.spmd import make_1d_mesh

    return make_1d_mesh(n_devices, axis)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def tp_param_spec(variables: PyTree, axis: str = "tp") -> PyTree:
    """PartitionSpec tree for a ``TransformerLM`` variables pytree."""

    def spec_for(path, leaf):
        names = _path_names(path)
        in_attn = any("MultiHeadAttention" in n for n in names)
        in_block = any(n.startswith("Block_") for n in names)
        leaf_name = names[-1]
        # which Dense inside its parent scope
        dense = next((n for n in names if n.startswith("Dense_")), None)
        if leaf_name == "kernel" and dense is not None:
            if in_attn:
                # qkv (Dense_0) column-parallel, output (Dense_1) row-parallel
                return P(None, axis) if dense == "Dense_0" else P(axis, None)
            if in_block:
                # MLP up (Dense_0) column-parallel, down (Dense_1) row-parallel
                return P(None, axis) if dense == "Dense_0" else P(axis, None)
        if leaf_name == "bias" and dense == "Dense_0" and in_block and not in_attn:
            return P(axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, variables)


def shard_tp_params(mesh: Mesh, variables: PyTree, axis: str = "tp") -> PyTree:
    """device_put the variables with the TP sharding plan."""
    specs = tp_param_spec(variables, axis)
    return jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)), variables, specs
    )


def tensor_parallel_lm(
    mesh: Mesh,
    *,
    vocab_size: int = 256,
    embed_dim: int = 128,
    num_heads: int = 4,
    num_layers: int = 2,
    seq_len: int = 256,
    axis: str = "tp",
):
    """Build (bundle, shard_params, apply, train_step) with TP shardings.

    ``shard_params(variables)`` lays the params out on the mesh;
    ``apply(variables, tokens)`` is the jitted forward (logits
    replicated); ``train_step(variables, tokens, targets, lr)`` is one
    jitted SGD step on the causal-LM loss whose gradients and updated
    params KEEP the TP sharding — XLA inserts the psums for the
    row-parallel matmuls in both passes.
    """
    # heads are sharded over ``axis`` by GSPMD: the lax attention, which
    # XLA can partition (a pallas_call it cannot)
    bundle = transformer_lm(
        vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
        num_layers=num_layers, seq_len=seq_len, attn_fn=lax_attention,
    )

    def shard_params(variables: PyTree) -> PyTree:
        return shard_tp_params(mesh, variables, axis)

    @jax.jit
    def apply(variables, tokens):
        logits = bundle.apply_eval(variables, tokens)
        return jax.lax.with_sharding_constraint(logits, NamedSharding(mesh, P()))

    def loss_fn(variables, tokens, targets):
        logits = bundle.apply_eval(variables, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            logp, targets[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        return nll.mean()

    @jax.jit
    def train_step(variables, tokens, targets, lr):
        loss, grads = jax.value_and_grad(loss_fn)(variables, tokens, targets)
        new_vars = jax.tree_util.tree_map(
            lambda p, g: p - lr * g.astype(p.dtype), variables, grads
        )
        return new_vars, loss

    return bundle, shard_params, apply, train_step
