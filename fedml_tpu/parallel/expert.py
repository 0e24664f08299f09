"""Expert parallelism: GShard-style mixture-of-experts FFN with
``all_to_all`` token dispatch over an ``ep`` mesh axis.

Completes the parallelism matrix the reference lacks entirely
(SURVEY.md §2.6 — TP/PP/SP/EP all "absent"): one expert's FFN weights
live on each device, tokens are data-sharded over the same axis, and a
pair of ``lax.all_to_all`` collectives routes each token to its top-1
expert and back.  Shapes are static: each token gets a position in its
expert's queue via a one-hot cumsum, tokens past ``capacity`` are
dropped (standard GShard semantics — the combine weight is zero, so a
dropped token contributes its residual path only).

All dispatch/combine math is einsum on one-hot masks — MXU-friendly,
no gathers/scatters with data-dependent shapes.

This is the capacity-dropping all-to-all demo, wired to the dry run only:
top-1, ReLU, one expert a device, no model calls it.  The expert layer a
model trains with is ``models/decoder.py``'s ``ExpertLayer``: softmax
routing with top-k and renormalisation, no dropped token, grouped products
over the experts it is told it holds (one chip's layer of an expert-parallel
deployment, without the exchange this file demonstrates).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def make_ep_mesh(n_devices: Optional[int] = None, axis: str = "ep") -> Mesh:
    from fedml_tpu.parallel.spmd import make_1d_mesh

    return make_1d_mesh(n_devices, axis)


def init_moe_params(
    key: jax.Array, num_experts: int, d_model: int, d_hidden: int
) -> PyTree:
    """Per-expert FFN weights stacked on a leading experts axis + gate."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_hidden)
    return {
        "gate": jax.random.normal(k3, (d_model, num_experts)) * scale_in,
        "w_in": jax.random.normal(k1, (num_experts, d_model, d_hidden)) * scale_in,
        "w_out": jax.random.normal(k2, (num_experts, d_hidden, d_model)) * scale_out,
    }


def shard_moe_params(mesh: Mesh, params: PyTree, axis: str = "ep") -> PyTree:
    """Experts sharded one-per-device-group; gate replicated."""
    return {
        "gate": jax.device_put(params["gate"], NamedSharding(mesh, P())),
        "w_in": jax.device_put(params["w_in"], NamedSharding(mesh, P(axis))),
        "w_out": jax.device_put(params["w_out"], NamedSharding(mesh, P(axis))),
    }


def _expert_ffn(w_in, w_out, x):
    return jnp.maximum(x @ w_in, 0.0) @ w_out


def make_moe_ffn(mesh: Mesh, capacity: int, axis: str = "ep"):
    """Build ``apply(params, x)`` for a top-1 MoE FFN.

    - params from ``init_moe_params`` with num_experts == mesh size,
      sharded by ``shard_moe_params``.
    - x: [T, d_model] tokens, sharded over ``axis`` on dim 0 (T divisible
      by the axis size).
    Returns [T, d_model]: gate_prob · FFN_{top1}(token), zeros for
    capacity-dropped tokens (callers add the residual).
    """
    E = mesh.shape[axis]

    def local(params, x):
        # params local shard: w_in/w_out [1, d, h]; gate replicated
        w_in, w_out = params["w_in"][0], params["w_out"][0]
        logits = x @ params["gate"]  # [t, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(logits, axis=-1)  # [t] top-1
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

        onehot_e = jax.nn.one_hot(expert, E, dtype=x.dtype)  # [t, E]
        # queue position of each token within its expert (local queue)
        pos = jnp.cumsum(onehot_e, axis=0) - onehot_e  # [t, E] rank if routed
        pos = (pos * onehot_e).sum(axis=1)  # [t]
        keep = (pos < capacity).astype(x.dtype)
        onehot_c = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=x.dtype)
        # dispatch mask [t, E, capacity]
        dispatch = onehot_e[:, :, None] * onehot_c[:, None, :] * keep[:, None, None]

        expert_in = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, cap, d]
        # route: each device sends slot e to device e, receives [E, cap, d]
        # where dim 0 is now the SOURCE device
        routed = lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=0, tiled=True
        )
        expert_out = _expert_ffn(w_in, w_out, routed.reshape(E * capacity, -1))
        expert_out = expert_out.reshape(E, capacity, -1)
        # route back: slot s returns to source device s
        returned = lax.all_to_all(
            expert_out, axis, split_axis=0, concat_axis=0, tiled=True
        )
        out = jnp.einsum("tec,ecd->td", dispatch, returned)
        return out * gate[:, None]

    param_specs = {"gate": P(), "w_in": P(axis), "w_out": P(axis)}
    sharded = shard_map(
        local, mesh=mesh, in_specs=(param_specs, P(axis)), out_specs=P(axis),
        check_vma=False,
    )

    def apply(params, x):
        n_experts = params["w_in"].shape[0]
        if n_experts != E:
            # P(axis) would hand each device a multi-expert shard of
            # which only [0] runs, and the gate would route tokens to
            # experts that never execute — wrong results, no error
            raise ValueError(
                f"params have {n_experts} experts but ep mesh size is {E}; "
                "one expert per device is required"
            )
        if x.shape[0] % E:
            raise ValueError(f"token count {x.shape[0]} not divisible by ep={E}")
        return sharded(params, x)

    return jax.jit(apply)


def moe_reference(params: PyTree, x: jax.Array) -> jax.Array:
    """Serial oracle (no capacity drops): gate_prob · FFN_{top1}(token)."""
    logits = x @ params["gate"]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(logits, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

    def one(tok, e, g):
        y = _expert_ffn(params["w_in"][e], params["w_out"][e], tok)
        return y * g

    return jax.vmap(one)(x, expert, gate)
