"""The dense matmuls of a configuration by group: what ``matmul_roofline``
credits to the whole ``matmul`` class, split by the part of the model step
that computes it.  A group is named as its scope is without the prefix
(``fedml_tpu/obs/scopes.py``: the ops of group ``head`` carry
``model.head``), so a reader joins a group's work to its seconds by name.

Nothing here knows a family by name.  ``products(config)`` asks the file
``dense_products/<family>.py``, found by the configuration's ``family`` key
as ``cells.load_family`` finds ``families/<family>.py``, for every dense
product a token passes as (in, out) widths, by group; a family that ships
no such file has no groups, and its readers say nothing.  The split is held
to the accepted yardstick where it is read, not only in a test: the groups'
FLOPs have to sum to the family's ``fwd_flops_per_unit(config)["matmul"]``
to the unit, or ``products`` raises.

Work is counted by the rule of ``families/*.train_bytes_per_unit``: 2 FLOPs
a multiply-add; every weight read forward and backward and its gradient
written once, over the step's ``batch_units``, activations in and out of a
product once a pass, 2 bytes.  The bytes only say which side bounds a group:
they are the products' own, and a family whose ``train_bytes_per_unit``
counts a width that no product has reads a little over them (its
``dense_products`` file says so)."""

from __future__ import annotations

import importlib

from benchmark import cells

PREFIX = "model."
PRODUCTS = "benchmark.dense_products."


def products(config: dict):
    """{group: [(in, out) of each dense product a token passes]}, or None for
    a family without a ``dense_products`` file."""
    name = PRODUCTS + config["family"]
    try:
        module = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:  # the file is there and lacks what it imports
            raise
        return None
    groups = module.products(config)
    ours = 2 * sum(i * o for p in groups.values() for i, o in p)
    theirs = cells.load_family(config).fwd_flops_per_unit(config)["matmul"]
    if ours != theirs:
        raise ValueError(
            f"dense_products/{config['family']}.py counts {ours} forward "
            f"FLOPs a unit and the family's matmul class {theirs}")
    return groups


def fwd_flops(config: dict) -> dict:
    """{group: forward FLOPs of one token}; empty without a file."""
    return {g: 2 * sum(i * o for i, o in p)
            for g, p in (products(config) or {}).items()}


def pass_bytes(config: dict, batch_units: int) -> dict:
    """{group: least HBM bytes of one token in ONE of a training step's three
    passes (forward, the input's gradient, the weights')}: a weight read or
    written once a pass over the step's ``batch_units``, activations in and
    out once."""
    return {g: 2 * (sum(i * o for i, o in p) / batch_units
                    + sum(i + o for i, o in p))
            for g, p in (products(config) or {}).items()}


def least_seconds(config: dict, group: str, units: float, batch_units: int,
                  pk: dict, passes: int = 3) -> float:
    """The least time a chip of peaks ``pk`` takes for ``passes`` passes of
    ``group``'s products over ``units`` tokens: 3 a training step, 1 its
    forward, 2 its backward."""
    f = passes * fwd_flops(config)[group] * units
    b = passes * pass_bytes(config, batch_units)[group] * units
    return max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])


def roofline(ctx, group: str):
    """Share (%) of its roofline of ``group`` in a traced run: least time for
    its FLOPs and bytes of the tokens computed (padding included, three
    passes) over the device seconds of the ``matmul``-class ops whose
    innermost scope is ``model.<group>``.  Reads only low: a fusion that
    takes another part's ops in adds seconds and no work.  None where no op
    carries the scope or the configuration has no such product."""
    from benchmark import model_scopes, peaks, traffic
    from benchmark.layer_metrics.step_mfu_pct import computed_units

    config = ctx.cell.config
    under = model_scopes.seconds(ctx, PREFIX + group, klass="matmul")
    if not under or not fwd_flops(config).get(group):
        return None
    batch_units = (ctx.cell.geometry["batch"]
                   * traffic.units_per_sample(config))
    least = least_seconds(config, group, computed_units(ctx), batch_units,
                          peaks.peaks(ctx.device_kind))
    return 100.0 * least / (under * len(ctx.summary.devices))
