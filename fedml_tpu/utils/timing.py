"""Shared benchmark timing methodology (bench.py, tools/bench_scaling.py).

JAX dispatch is asynchronous, so every timed region ends in
``sync_round``: it blocks on the outputs AND reads every metric leaf back
to the host.  The readback is also the finiteness check — a NaN in any
metric poisons the returned sum and ``measure_rounds`` raises.

``measure_rounds`` warms up until two consecutive fully-synced calls
agree, because the first call compiles and the second may compile again
(the state it is fed back is device-committed, a new jit signature).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Tuple

import numpy as np


def sync_round(state: Any, metrics: Any) -> float:
    """Block on the outputs AND read the scalars back.  Returns the sum
    over ALL metric leaves — a NaN/inf in any metric (loss included)
    poisons the result so the caller's finiteness check fires."""
    import jax

    jax.block_until_ready((state, metrics))
    # np.asarray first: np.sum of a jax array dispatches to the device
    # and compiles a reduction of its own
    return float(sum(float(np.sum(np.asarray(l)))
                     for l in jax.tree_util.tree_leaves(metrics)))


def measure_rounds(
    round_fn: Callable,
    state: Any,
    args_dev: Tuple,
    rounds: int,
    *,
    max_warmup: int = 6,
    agree_rtol: float = 0.2,
) -> Tuple[float, Any]:
    """(median seconds per fully-synced round, final state)."""
    prev = None
    for i in range(max_warmup):
        t0 = time.perf_counter()
        state, m = round_fn(state, *args_dev)
        sync_round(state, m)
        dt = time.perf_counter() - t0
        # agreement counts only from call 3 on: the two calls that may
        # compile can agree with each other
        if i >= 2 and prev is not None and abs(dt - prev) / max(dt, prev) < agree_rtol:
            break
        prev = dt
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        state, m = round_fn(state, *args_dev)
        scalar = sync_round(state, m)
        times.append(time.perf_counter() - t0)
        if not np.isfinite(scalar):
            raise FloatingPointError(
                f"benchmark round produced non-finite metrics: {scalar}"
            )
    return float(np.median(times)), state
