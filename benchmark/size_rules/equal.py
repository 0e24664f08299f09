"""Size rule ``equal``: every client holds ``steps`` x ``batch`` samples.

A size rule is found by ``geometry["sizes"]["kind"]`` and exposes
``client_sizes(geometry, rng) -> [clients]`` samples held by each client;
``rng`` comes from ``--seed``, for rules that draw."""

import numpy as np


def client_sizes(geometry: dict, rng: np.random.Generator) -> np.ndarray:
    n = geometry["sizes"]["steps"] * geometry["batch"]
    return np.full(geometry["clients"], n, np.int64)
