"""DP×TP federated rounds on a 2-D (clients, model) mesh via GSPMD.

The shard_map round (``parallel/spmd.py``) keeps server state fully
replicated — right for the small-model FL matrix, impossible for models
that don't fit one chip.  This module runs the SAME round function
(``algorithms.fedavg.make_round_fn``) under plain ``jit`` with sharding
annotations instead: the packed client block is sharded over the
``clients`` axis, the transformer parameters over the ``model`` axis
(Megatron column/row plan from ``parallel/tensor.py``), and the GSPMD
partitioner derives every collective — client-parallel local scans,
tensor-sharded matmuls inside each client's forward/backward, and the
cross-client weighted aggregation — from those annotations alone.

This is the cross-silo "federated fine-tuning of a model bigger than
one chip" capability; the reference's process-per-client MPI design has
no analogue (SURVEY.md §2.6).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
from fedml_tpu.core.client import LocalUpdateFn
from fedml_tpu.parallel.mesh import device_grid
from fedml_tpu.parallel.tensor import tp_param_spec

PyTree = Any


def make_dp_tp_mesh(
    n_clients_axis: int, n_model_axis: int, *, devices=None
) -> Mesh:
    return Mesh(device_grid((n_clients_axis, n_model_axis), devices),
                axis_names=("clients", "model"))


def opt_state_sharding_like(
    mesh: Mesh,
    variables_template: PyTree,
    opt_state_template: PyTree,
    axis: str = "model",
    *,
    pspec: Optional[PyTree] = None,
) -> PyTree:
    """Sharding tree for server-optimizer state whose leaves mirror the
    parameters (FedAdam/FedYogi moments): each opt leaf with the shape
    of some param leaf inherits that param's TP spec; everything else
    (counts, scalars) is replicated.  Shape-based matching is a
    heuristic — two same-shaped params with different specs resolve to
    whichever appears first, which only changes layout, not values.

    ``pspec`` overrides the param spec tree (the partition-rule engine
    in ``parallel/partition.py`` passes its rule-derived specs here);
    the default keeps the transformer TP heuristic."""
    if pspec is None:
        pspec = tp_param_spec(variables_template, axis)
    shape_to_spec = {}
    for leaf, spec in zip(
        jax.tree_util.tree_leaves(variables_template),
        jax.tree_util.tree_leaves(pspec, is_leaf=lambda x: isinstance(x, P)),
    ):
        shape_to_spec.setdefault(np.shape(leaf), spec)
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, shape_to_spec.get(np.shape(l), P())),
        opt_state_template,
    )


def make_dp_tp_round_fn(
    mesh: Mesh,
    local_update: LocalUpdateFn,
    variables_template: PyTree,
    *,
    server_update=None,
    aggregate_transform=None,
    opt_state_sharding: Optional[PyTree] = None,
):
    """jit the FedAvg round with data over ``clients`` and transformer
    params over ``model``.

    ``variables_template`` (an unsharded init) fixes the param sharding
    plan.  Returns (round_fn, shard_state, shard_data):
    ``shard_state(state)`` lays server state out on the mesh;
    ``shard_data(arrays)`` shards the packed client block.  The returned
    state from ``round_fn`` keeps the same shardings (donated input).

    When a ``server_update`` carries parameter-sized optimizer state
    (FedAdam moments), pass ``opt_state_sharding`` (see
    ``opt_state_sharding_like``) — the default replicates opt_state,
    which would defeat the bigger-than-one-chip purpose for such state.
    """
    kwargs = {}
    if server_update is not None:
        kwargs["server_update"] = server_update
    # no axis_name: aggregation is the einsum over the packed K axis —
    # GSPMD partitions it over `clients` and inserts the reduce itself.
    # vmap (not lax.map) over the client axis so the partitioner can
    # split the K dim across the mesh instead of serializing it.
    inner = make_round_fn(
        local_update,
        aggregate_transform=aggregate_transform,
        client_axis_impl="vmap",
        **kwargs,
    )

    pspec = tp_param_spec(variables_template, axis="model")
    var_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec
    )
    repl = NamedSharding(mesh, P())
    data_sharding = NamedSharding(mesh, P("clients"))

    state_sharding = ServerState(
        variables=var_sharding,
        opt_state=opt_state_sharding if opt_state_sharding is not None else repl,
        round_idx=repl,
        key=repl,
    )

    def shard_state(state: ServerState) -> ServerState:
        return jax.device_put(state, state_sharding)

    def shard_data(arrays):
        return tuple(jax.device_put(np.asarray(a), data_sharding)
                     for a in arrays)

    round_fn = jax.jit(
        inner,
        in_shardings=(state_sharding, data_sharding, data_sharding,
                      data_sharding, data_sharding, data_sharding,
                      data_sharding),
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,),
    )
    return round_fn, shard_state, shard_data
