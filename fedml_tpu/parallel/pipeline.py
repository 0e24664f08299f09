"""Pipeline parallelism: GPipe-style microbatched stage pipeline over a
``pp`` mesh axis.

The reference's only inter-layer model split is SplitNN's 2-stage
client/server relay, which crosses a PROCESS boundary twice per
mini-batch (``split_nn/client.py:24-34``, ``server.py:40-59`` — SURVEY.md
§3.3 calls it the latency-critical pattern).  Here the generalization to
S stages runs as ONE compiled SPMD program: each device owns one stage's
parameters, activations rotate stage→stage+1 with ``lax.ppermute`` on
the ICI ring, and microbatches keep every stage busy outside the
fill/drain bubble.  The schedule is the standard masked-tick loop:
at tick t, stage s computes microbatch (t − s); invalid ticks are
bubbles masked with ``jnp.where`` (no data-dependent control flow, so
XLA compiles a single static loop).

Differentiable end-to-end: ``ppermute``'s transpose is the reverse
permute, so ``jax.grad`` through ``apply`` yields per-stage parameter
gradients — pipeline-parallel training, not just inference.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# stage_fn(stage_params, x[B, ...]) -> y[B, ...]  (same activation shape
# across stage boundaries, as in equal-depth transformer stages)
StageFn = Callable[[PyTree, jax.Array], jax.Array]


def make_pp_mesh(n_devices: Optional[int] = None, axis: str = "pp") -> Mesh:
    from fedml_tpu.parallel.spmd import make_1d_mesh

    return make_1d_mesh(n_devices, axis)


def stack_stage_params(stage_params_list) -> PyTree:
    """Stack S per-stage param pytrees along a new leading axis (the axis
    ``shard_stage_params`` lays out one-stage-per-device)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *stage_params_list
    )


def shard_stage_params(mesh: Mesh, stacked: PyTree, axis: str = "pp") -> PyTree:
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda v: jax.device_put(v, sharding), stacked
    )


def make_gpipe(mesh: Mesh, stage_fn: StageFn, axis: str = "pp"):
    """Build ``apply(stacked_stage_params, x_microbatches)``.

    - ``stacked_stage_params``: leaves [S, ...], sharded one stage per
      device on ``axis`` (see ``stack_stage_params``/``shard_stage_params``).
    - ``x_microbatches``: [M, B, ...] replicated; M microbatches.
    Returns y [M, B, ...] (replicated), equal to running the S stages
    sequentially over each microbatch.
    """
    S = mesh.shape[axis]

    def local(params_local, x):
        sid = lax.axis_index(axis)
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        M = x.shape[0]
        ticks = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            recv, out = carry
            # stage 0 injects fresh microbatch t; others consume what
            # stage s-1 computed last tick
            x_t = lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, M - 1), keepdims=False
            )
            inp = jnp.where(sid == 0, x_t, recv)
            y = stage_fn(p, inp)
            nxt = lax.ppermute(y, axis, perm)
            # last stage emits microbatch t-(S-1) once it's valid
            out_idx = t - (S - 1)
            valid = (sid == S - 1) & (out_idx >= 0)
            oi = jnp.clip(out_idx, 0, M - 1)
            emitted = lax.dynamic_update_index_in_dim(out, y, oi, 0)
            out = jnp.where(valid, emitted, out)
            return (nxt, out), None

        init = (jnp.zeros(x.shape[1:], x.dtype), jnp.zeros_like(x))
        (_, out), _ = lax.scan(tick, init, jnp.arange(ticks))
        # outputs live on the last stage only; psum-broadcast to all
        out = lax.psum(
            jnp.where(sid == S - 1, out, jnp.zeros_like(out)), axis
        )
        return out

    sharded = shard_map(
        local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    )
    jitted = jax.jit(sharded)

    def apply(stacked_stage_params, x_microbatches):
        n_stages = jax.tree_util.tree_leaves(stacked_stage_params)[0].shape[0]
        if n_stages != S:
            # P(axis) would silently hand each device a multi-stage shard
            # of which only [0] runs — wrong results, no error
            raise ValueError(
                f"stacked stage count {n_stages} != pp mesh size {S}; "
                "one stage per device is required"
            )
        return jitted(stacked_stage_params, x_microbatches)

    return apply


def serial_reference(stage_fn: StageFn, stacked: PyTree, x: jax.Array):
    """Run the same stages sequentially (the correctness oracle)."""
    S = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def one_mb(xb):
        h = xb
        for s in range(S):
            p = jax.tree_util.tree_map(lambda a: a[s], stacked)
            h = stage_fn(p, h)
        return h

    return jax.vmap(one_mb)(x)
