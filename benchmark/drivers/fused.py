"""Cross-silo driver: the whole cohort resident on the device and one
round a call through ``make_multi_round_fn``, the program of
``FedAvgSimulation.run_fused``, jitted as that jits it (no donation).  One
round, because a round of a model that fills the chip is seconds long
(fusing more buys nothing) and the reference is compared with one call."""

from __future__ import annotations

from benchmark import cells, traffic
from benchmark.drivers import base


def build_round_fn(cell, bundle, mesh=None):
    import jax

    from fedml_tpu.algorithms.fedavg import make_multi_round_fn

    return jax.jit(make_multi_round_fn(
        cells.build_local_update(cell.config, bundle), 1))


class Session(base.BaseSession):
    def __init__(self, cell, seed, devices):
        import jax

        self.cell = cell
        self.bundle = cells.build_bundle(cell.config)
        self.state = base.seeded_state(self.bundle, seed)
        self.round_fn = build_round_fn(cell, self.bundle)
        self.block = jax.device_put(
            traffic.resident_block(cell.config, cell.geometry, seed))
        self.cohort = cell.geometry["cohort"]

    def reference_round(self, block):
        import jax

        state, metrics = self.round_fn(self.state, *jax.device_put(block))
        return state.variables, base.host_metrics(metrics)
