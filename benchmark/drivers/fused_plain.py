"""The cross-silo driver of ``fused.py`` for a family that brings a plain
reference of its own: the round program is built from the program's bundle,
and ``bundle`` (read only by ``run.py:check_reference``) is the family's
plain forward pass over the same parameter tree."""

from __future__ import annotations

from benchmark import cells
from benchmark.drivers import fused

build_round_fn = fused.build_round_fn


class Session(fused.Session):
    def __init__(self, cell, seed, devices):
        super().__init__(cell, seed, devices)
        self.bundle = cells.load_family(cell.config).plain_bundle(cell.config)
