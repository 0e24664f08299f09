"""What the harness asks of a driver's ``Session``.  A driver module
``drivers/<name>.py`` defines ``Session(cell, seed, devices)`` on this base
and ``build_round_fn`` for the compile-only rehearsal."""

from __future__ import annotations

import numpy as np


class BaseSession:
    """As it stands, a session over a cohort resident on the device: ``state``
    (the program's ServerState), ``round_fn`` (jitted; one call runs
    ``rounds_per_call`` rounds) and ``block`` (the packed cohort)."""

    cell = None
    bundle = None
    cohort = 0  # participants every round must report
    state = None
    round_fn = None
    block = None
    rounds_per_call = 1

    def call(self):
        """One call into the program, fully synced.  Returns (rounds run,
        {metric: host array with one entry a round})."""
        import jax

        self.state, metrics = self.round_fn(self.state, *self.block)
        jax.block_until_ready(self.state)
        return self.rounds_per_call, host_metrics(metrics)

    def reference_round(self, block):
        """One round of this driver's program on ``block`` (host arrays in
        the round functions' layout) from the current state, which it leaves
        as it was.  Returns (new variables, host metrics)."""
        raise NotImplementedError

    def padded_samples_per_round(self) -> int:
        return int(self.block[2].size)

    def round_idx(self) -> int:
        return int(np.asarray(self.state.round_idx))


def host_metrics(metrics: dict) -> dict:
    return {k: np.asarray(v).reshape(-1) for k, v in metrics.items()}


def seeded_state(bundle, seed: int):
    """Weights made on the device in one jitted call from the seed."""
    import jax

    from benchmark import cells

    return jax.jit(lambda k: cells.initial_state(bundle, k))(
        jax.random.PRNGKey(seed))
