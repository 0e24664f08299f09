"""Operations and bytes an algorithm needs, computed from a configuration
file's shapes by its family's module (``families/<family>.py``).  Training
counts forward + backward = 3x forward; recomputation is never counted.
The accounting lives under ``benchmark/`` so that no PR that claims a gain
can change it."""

from __future__ import annotations

from benchmark import cells


def train_flops_per_unit(config: dict) -> dict:
    """Forward + backward FLOPs by op class for one unit of the throughput
    metric: a token of a language model, a sample of an image model."""
    fwd = cells.load_family(config).fwd_flops_per_unit(config)
    return {k: 3 * v for k, v in fwd.items()}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """Least HBM bytes by op class for one unit, in a step of
    ``batch_units``; an op class the family gives no bytes is FLOP-bound."""
    return cells.load_family(config).train_bytes_per_unit(config, batch_units)
