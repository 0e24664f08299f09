"""Cross-chip driver: one round a call through ``make_spmd_round_fn`` on a
``clients`` mesh over the cell's chips (``make_client_mesh``,
``shard_client_block``, ``replicate``, as ``chip_smoke.py``'s spmd leg): the
cohort is split over the chips, the weighted sums are ``psum``-ed, the state
is replicated and donated."""

from __future__ import annotations

from benchmark import cells, traffic
from benchmark.drivers import base


def build_round_fn(cell, bundle, mesh):
    from fedml_tpu.parallel.spmd import make_spmd_round_fn

    return make_spmd_round_fn(
        mesh, cells.build_local_update(cell.config, bundle))


def replica_checksum(tree):
    """Sum of every leaf's bits as uint32, computed where the leaves live."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def checksum(t):
        return sum(jnp.sum(jax.lax.bitcast_convert_type(l, jnp.uint32))
                   for l in jax.tree_util.tree_leaves(t))

    return checksum(tree)


class Session(base.BaseSession):
    def __init__(self, cell, seed, devices):
        from fedml_tpu.parallel.spmd import (make_client_mesh, replicate,
                                             shard_client_block)

        self.cell = cell
        self.bundle = cells.build_bundle(cell.config)
        self.mesh = make_client_mesh(len(devices), devices=devices)
        self.round_fn = build_round_fn(cell, self.bundle, self.mesh)
        self.state = replicate(self.mesh,
                               base.seeded_state(self.bundle, seed))
        self.block = shard_client_block(self.mesh, traffic.resident_block(
            cell.config, cell.geometry, seed))
        spread = {len(a.sharding.device_set) for a in self.block}
        if spread != {len(devices)}:
            raise RuntimeError(f"cohort not spread over {len(devices)} "
                               f"devices: {spread}")
        self.cohort = cell.geometry["cohort"]

    def reference_round(self, block):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.parallel.spmd import shard_client_block

        # the round donates its state: hand it a copy
        state, metrics = self.round_fn(
            jax.tree_util.tree_map(jnp.copy, self.state),
            *shard_client_block(self.mesh, block))
        sums = {int(replica_checksum(jax.tree_util.tree_map(
            lambda l: l.addressable_shards[d].data, state.variables)))
            for d in range(len(self.mesh.devices.flat))}
        if len(sums) != 1:
            raise RuntimeError(f"new state differs between devices: {sums}")
        return state.variables, base.host_metrics(metrics)
