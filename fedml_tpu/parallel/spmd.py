"""SPMD execution of federated rounds over a device mesh.

This is the ComManager replacement the BASELINE.json north star names:
the reference's one-MPI-process-per-participant layout
(``FedAvgAPI.py:10-25`` + ``run_fedavg_distributed_pytorch.sh:19-23``)
becomes one SPMD program on a ``clients`` mesh axis.  Model sync is
replication (no explicit broadcast messages); upload + aggregate is a
masked weighted ``lax.psum``; subsampling is a collective mask.  A
``model`` axis is reserved in the mesh so tensor/pipeline extensions
don't force a redesign (SURVEY.md §2.6).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import make_round_fn
from fedml_tpu.core.client import LocalUpdateFn
from fedml_tpu.parallel.mesh import device_grid

PyTree = Any


def make_1d_mesh(n_devices: Optional[int] = None, axis: str = "x") -> Mesh:
    """1-D mesh over the first n devices (shared by the tp/pp/sp/ep
    constructors)."""
    n = n_devices if n_devices is not None else jax.device_count()
    return Mesh(device_grid((n,)), (axis,))


def make_client_mesh(
    num_devices: Optional[int] = None, *, model_axis: int = 1, devices=None
) -> Mesh:
    """Mesh with a ``clients`` data axis and a reserved ``model`` axis."""
    devices = devices if devices is not None else jax.devices()
    n = num_devices if num_devices is not None else len(devices)
    if n % model_axis:
        raise ValueError(f"{n} devices not divisible by model axis {model_axis}")
    return Mesh(device_grid((n // model_axis, model_axis), devices),
                axis_names=("clients", "model"))


def make_spmd_round_fn(
    mesh: Mesh,
    local_update: LocalUpdateFn,
    *,
    server_update=None,
    aggregate_transform=None,
    donate: bool = True,
):
    """shard_map the round over the ``clients`` mesh axis.

    Data layout: the packed client block [C, steps, B, ...] is sharded on
    its leading axis; each device vmaps over its local C/D clients, then
    the weighted tree-sums are psum'd across the axis.  Server state is
    fully replicated, so the returned new state is identical on every
    device — broadcast of the next round's model is free.
    """
    kwargs = {}
    if server_update is not None:
        kwargs["server_update"] = server_update
    inner = make_round_fn(
        local_update,
        aggregate_transform=aggregate_transform,
        axis_name="clients",
        **kwargs,
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(),  # state replicated
            P("clients"),  # x
            P("clients"),  # y
            P("clients"),  # mask
            P("clients"),  # num_samples
            P("clients"),  # participation
            P("clients"),  # global slot ids
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def spmd_round(state, x, y, mask, num_samples, participation, slot_ids):
        return inner(state, x, y, mask, num_samples, participation, slot_ids)

    return jax.jit(spmd_round, donate_argnums=(0,) if donate else ())


def shard_client_block(mesh: Mesh, pack_arrays):
    """device_put packed [C, ...] arrays sharded over the clients axis.

    Host arrays go from host memory straight to their shards:
    ``jnp.asarray`` first would commit the whole block to device 0 and
    re-shard from there, which a cohort sized for the mesh may not fit."""
    sharding = NamedSharding(mesh, P("clients"))
    return tuple(
        jax.device_put(a if isinstance(a, jax.Array) else np.asarray(a),
                       sharding)
        for a in pack_arrays
    )


def _devices_by_clients_index(mesh: Mesh):
    """mesh.devices grouped by clients-axis index, regardless of where
    the ``clients`` axis sits in ``mesh.axis_names`` (positional
    ``mesh.devices[i]`` would silently walk the wrong axis for a
    ('model', 'clients') mesh)."""
    ax = mesh.axis_names.index("clients")
    moved = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    return [list(moved[i].flat) for i in range(moved.shape[0])]


def host_client_range(
    mesh: Mesh,
    num_slots: int,
    *,
    process_index: Optional[int] = None,
    host_of_device=None,
) -> range:
    """The contiguous client-slot range owned by this host's devices.

    Under ``NamedSharding(mesh, P("clients"))`` slot ``k`` lives on the
    devices at clients-axis index ``k // (num_slots / n_clients_axis)``.
    A host's slots are the union over its devices — the per-rank
    partition of the reference's distributed loaders
    (``cifar10/data_loader.py:201-233``), derived from the mesh instead
    of an MPI rank argument.

    ``host_of_device`` maps a device to its host id (default: the real
    ``device.process_index``); tests inject a fake mapping to simulate a
    multi-host pod on a single-process CPU mesh.
    """
    if host_of_device is None:
        host_of_device = lambda d: d.process_index  # noqa: E731
    if process_index is None:
        process_index = jax.process_index()
    n_cl = mesh.shape["clients"]
    if num_slots % n_cl:
        raise ValueError(f"{num_slots} slots not divisible by clients axis {n_cl}")
    block = num_slots // n_cl
    dev_rows = _devices_by_clients_index(mesh)
    mine = [
        i
        for i in range(n_cl)
        if any(host_of_device(d) == process_index for d in dev_rows[i])
    ]
    if not mine:
        return range(0)
    lo, hi = min(mine), max(mine)
    if mine != list(range(lo, hi + 1)):
        raise ValueError(
            "host's devices are not contiguous along the clients axis; "
            "reorder the mesh so each host owns one slot range"
        )
    return range(lo * block, (hi + 1) * block)


def shard_client_block_local(
    mesh: Mesh,
    num_slots: int,
    shards_by_slot_start,
):
    """Assemble globally-sharded [C, ...] arrays from per-host blocks.

    ``shards_by_slot_start`` maps a slot start to the tuple of host
    arrays covering a contiguous slot range (each host contributes the
    range from its ``host_client_range`` and NEVER materializes the
    rest).  The global ``jax.Array`` is built with
    ``jax.make_array_from_single_device_arrays``, whose contract is
    exactly this: every process supplies only its addressable shards.
    (A single-process test passes all ranges, split across simulated
    hosts upstream.)
    """
    sharding = NamedSharding(mesh, P("clients"))
    n_cl = mesh.shape["clients"]
    block = num_slots // n_cl
    if not shards_by_slot_start:
        # A host whose devices are outside this mesh owns no slot range
        # (host_client_range -> range(0)) — but such a host also has no
        # addressable shards here and cannot legally participate in a
        # computation over this mesh at all; assembling from it is a
        # caller bug, not a degenerate case to paper over.
        raise ValueError(
            "no slot ranges supplied; a host with host_client_range() == "
            "range(0) has no devices in this mesh and must not join its "
            "computations"
        )
    n_arrays = len(next(iter(shards_by_slot_start.values())))
    # slot start -> (host array tuple, offset of that device block inside it)
    covering = {}
    for start, arrays in shards_by_slot_start.items():
        rows = np.asarray(arrays[0]).shape[0]
        if start % block or rows % block:
            raise ValueError(
                f"range [{start}, {start + rows}) is not aligned to the "
                f"per-device block of {block} slots"
            )
        for i in range(start // block, (start + rows) // block):
            covering[i * block] = (arrays, i * block - start)
    dev_rows = _devices_by_clients_index(mesh)
    out = []
    for j in range(n_arrays):
        buffers = []
        sample = None
        for i in range(n_cl):
            entry = covering.get(i * block)
            if entry is None:
                continue  # another host's range (its process supplies it)
            arrays, off = entry
            # a host array: device_put copies it to each owner directly
            piece = np.asarray(arrays[j])[off : off + block]
            sample = piece
            for d in dev_rows[i]:
                buffers.append(jax.device_put(piece, d))
        global_shape = (num_slots,) + tuple(sample.shape[1:])
        out.append(
            jax.make_array_from_single_device_arrays(
                global_shape, sharding, buffers
            )
        )
    return tuple(out)


def replicate(mesh: Mesh, tree: PyTree) -> PyTree:
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


# ---------------------------------------------------------------------------
# Hierarchical (two-tier) FL on a nested (group, clients) mesh
# ---------------------------------------------------------------------------


def make_group_mesh(num_groups: int, n_devices: Optional[int] = None) -> Mesh:
    """Nested mesh for two-tier FL: ``group`` (slow axis — slices/DCN)
    × ``clients`` (fast axis — chips within a slice/ICI)."""
    n = n_devices if n_devices is not None else jax.device_count()
    if n % num_groups:
        raise ValueError(f"{n} devices not divisible into {num_groups} groups")
    return Mesh(device_grid((num_groups, n // num_groups)),
                axis_names=("group", "clients"))


def hierarchical_pack(dataset, groups, batch_size, steps_per_epoch, seed):
    """Stack per-group device-resident packs into one [G*C, ...] block
    in group-major order (the ``P(("group", "clients"))`` layout), plus
    the matching global slot ids.  Uses the exact per-group pack the
    host simulation builds (``HierarchicalSimulation._group_pack``), so
    the SPMD program sees bit-identical client shards."""
    from fedml_tpu.core.types import device_resident_pack

    sizes = {g: len(ids) for g, ids in groups.items()}
    if len(set(sizes.values())) != 1:
        raise ValueError(
            f"nested-mesh hierarchical FL needs equal group sizes, got "
            f"{sizes}; pad the grouping or drop stragglers"
        )
    blocks, all_ids = [], []
    for g in sorted(groups):
        ids = np.asarray(groups[g])
        args, _ = device_resident_pack(
            dataset, ids, batch_size, steps_per_epoch=steps_per_epoch,
            seed=seed,
        )
        blocks.append(args)
        all_ids.append(ids)
    stacked = tuple(
        jnp.concatenate([jnp.asarray(b[i]) for b in blocks], axis=0)
        for i in range(len(blocks[0]))
    )
    return stacked, np.concatenate(all_ids)


def make_hierarchical_spmd_round_fn(
    mesh: Mesh,
    local_update: LocalUpdateFn,
    *,
    group_comm_round: int,
    server_update=None,
    aggregate_transform=None,
):
    """One GLOBAL hierarchical round as ONE shard_map program on a
    (``group``, ``clients``) mesh — the SURVEY §2.6 mapping the host
    simulation (``algorithms/hierarchical.py``) documents: every group
    starts from the global model, runs ``group_comm_round`` in-group
    FedAvg rounds whose aggregation is a masked-psum over the
    ``clients`` axis ONLY (intra-slice, rides ICI), and the global tier
    is one sample-weighted psum over the ``group`` axis (inter-slice,
    rides DCN) at the end.  Reference semantics:
    ``standalone/hierarchical_fl/trainer.py:43-69`` +
    ``group.py:24-46``.

    Parity contract (certified in the driver dryrun and
    ``tests/test_spmd.py``): with data laid out by ``hierarchical_pack``
    this program's output equals ``HierarchicalSimulation.run_round``
    exactly — same per-group key schedule
    (``fold_in(state.key, 1000 + g)``), same in-group round_idx base
    (``round_idx * group_comm_round``), same group weights (the group's
    total sample count).
    """
    kwargs = {}
    if server_update is not None:
        kwargs["server_update"] = server_update
    inner = make_round_fn(
        local_update,
        aggregate_transform=aggregate_transform,
        axis_name="clients",
        **kwargs,
    )
    from fedml_tpu.algorithms.fedavg import ServerState

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(),                      # state replicated
            P(("group", "clients")),  # x   [G*C, steps, B, ...]
            P(("group", "clients")),  # y
            P(("group", "clients")),  # mask
            P(("group", "clients")),  # num_samples
            P(("group", "clients")),  # participation
            P(("group", "clients")),  # global slot ids
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def hier_round(state, x, y, mask, num_samples, participation, slot_ids):
        g = jax.lax.axis_index("group")
        gstate = ServerState(
            variables=state.variables,
            opt_state=state.opt_state,
            round_idx=state.round_idx * group_comm_round,
            key=jax.random.fold_in(state.key, 1000 + g),
        )

        def in_group_round(gs, _):
            return inner(gs, x, y, mask, num_samples, participation,
                         slot_ids)

        gstate, ms = jax.lax.scan(
            in_group_round, gstate, None, length=group_comm_round
        )
        # global tier: group models weighted by the group's TOTAL sample
        # count (reference group.py aggregates over the whole group)
        group_total = jax.lax.psum(num_samples.sum(), "clients")
        num = jax.tree_util.tree_map(
            lambda leaf: jax.lax.psum(
                group_total * leaf.astype(jnp.float32), "group"
            ),
            gstate.variables,
        )
        den = jax.lax.psum(group_total, "group")
        new_vars = jax.tree_util.tree_map(
            lambda s, ref: (s / jnp.maximum(den, 1e-12)).astype(ref.dtype),
            num,
            state.variables,
        )
        # host parity: metrics accumulate over EVERY in-group round of
        # every group (inner already psums across clients)
        metrics = {k: jax.lax.psum(v.sum(), "group") for k, v in ms.items()}
        new_state = ServerState(
            variables=new_vars,
            opt_state=state.opt_state,
            round_idx=state.round_idx + 1,
            key=state.key,
        )
        return new_state, metrics

    return jax.jit(hier_round)
