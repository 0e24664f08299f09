"""The one traffic generator.  A mix is the ``geometry`` block of a workload
file; everything random comes from ``--seed``.  What a sample is comes from
the configuration's family (``families/<family>.py``) and what each client
holds from the geometry's size rule (``size_rules/<kind>.py``), both found
by name.

geometry keys
  clients   population size
  cohort    clients a round (== clients: full participation)
  batch     per-step batch
  sizes     {"kind": <size rule>, ...the rule's own parameters}
"""

from __future__ import annotations

import numpy as np

from benchmark import cells


def client_sizes(geometry: dict, seed: int) -> np.ndarray:
    """[clients] samples held by each client."""
    sizes = np.asarray(cells.load_size_rule(geometry).client_sizes(
        geometry, np.random.default_rng([seed, 3])))
    if sizes.shape != (geometry["clients"],) or (sizes < 1).any():
        raise ValueError(f"size rule {geometry['sizes']['kind']!r} gave "
                         f"{sizes.shape} sizes (least {sizes.min()}) for "
                         f"{geometry['clients']} clients")
    return sizes


def steps_per_epoch(geometry: dict, seed: int) -> int:
    """Steps every client of a cohort runs: the largest client's."""
    return int(-(-client_sizes(geometry, seed).max() // geometry["batch"]))


def make_samples(config: dict, n: int, seed: int):
    """(x [n, ...], y [n, ...]) host arrays of ``n`` training samples in the
    input shape of the configuration's family."""
    return cells.load_family(config).make_samples(
        config, n, np.random.default_rng([seed, 2]))


def resident_block(config: dict, geometry: dict, seed: int):
    """The packed block of a full-participation cohort, as the program's
    round functions take it: (x, y, mask, num_samples, participation,
    slot_ids) with leading [clients, steps, batch]."""
    sizes = client_sizes(geometry, seed)
    k, b = geometry["clients"], geometry["batch"]
    s = steps_per_epoch(geometry, seed)
    if (sizes != s * b).any():
        raise ValueError("a resident block holds clients of equal size")
    x, y = make_samples(config, k * s * b, seed)
    return (x.reshape(k, s, b, *x.shape[1:]), y.reshape(k, s, b, *y.shape[1:]),
            np.ones((k, s, b), np.float32), np.full((k,), s * b, np.float32),
            np.ones((k,), np.float32), np.arange(k, dtype=np.int32))


def units_per_sample(config: dict) -> int:
    """What the throughput metric counts for one sample: its tokens, or 1."""
    return int(cells.load_family(config).units_per_sample(config))
