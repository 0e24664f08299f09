"""Cells as data: a cell is ``workloads/<name>.json``, which names its
configuration ``configs/<config>.json`` and its driver
``drivers/<driver>.py``; the configuration names its model family
``families/<family>.py`` and the geometry its size rule
``size_rules/<kind>.py``.  Nothing here knows a cell, a configuration, a
family or a traffic mix by name, so a later PR adds one by adding files."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)


def read_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict  # workloads/<name>.json as written
    config: dict  # configs/<config>.json, the rehearsal's overrides applied
    geometry: dict  # the cell's geometry, or its rehearsal's
    reference: dict  # the reduced cohort compared with the plain reference
    rehearsal: bool

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    workload = read_json("workloads", f"{name}.json")
    config = read_json("configs", f"{workload['config']}.json")
    geometry, reference = workload["geometry"], workload["reference"]
    if rehearsal:
        toy = workload["rehearsal"]
        config = {**config, **toy["config"]}
        geometry = {**geometry, **toy["geometry"]}
        reference = {**reference, **toy.get("reference", {})}
    return Cell(name, workload, config, geometry, reference, rehearsal)


def load_driver(name: str):
    return importlib.import_module(f"benchmark.drivers.{name}")


def load_layer_metric(name: str):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def load_family(config: dict):
    """The module of the configuration's model family: how to build the
    program's model at the file's sizes, what a sample of its input is, and
    what it costs (``families/transformer_lm.py`` says what one exposes)."""
    return importlib.import_module(f"benchmark.families.{config['family']}")


def load_size_rule(geometry: dict):
    """The module of the geometry's size rule: samples held by each client."""
    return importlib.import_module(
        f"benchmark.size_rules.{geometry['sizes']['kind']}")


# -- the system under test, built from a configuration file -----------------

def build_bundle(config: dict):
    """The program's own model for the configuration's family, at the
    file's sizes: what ``experiments/registry.py`` hands a user."""
    return load_family(config).build_bundle(config)


def build_local_update(config: dict, bundle):
    from fedml_tpu.algorithms.fedavg import resolve_compute_dtype
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    opt = config["optimizer"]
    optimizer = make_client_optimizer(
        opt["name"], opt["lr"], momentum=opt.get("momentum", 0.0),
        weight_decay=opt.get("weight_decay"))
    return make_local_update(
        bundle, optimizer, epochs=1,
        compute_dtype=resolve_compute_dtype(config["compute_dtype"]))


def initial_state(bundle, key):
    """Server state with weights from ``key``; jit it and the weights are
    made on the device in one call."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState

    return ServerState(variables=bundle.init(key), opt_state=(),
                       round_idx=jnp.zeros((), jnp.int32), key=key)
