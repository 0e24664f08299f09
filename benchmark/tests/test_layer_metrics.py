"""Every per-layer reader, on the recorded trace with a stand-in session."""

import copy
import dataclasses
import os
import types

import numpy as np
import pytest

from benchmark import cells, flops, trace_reduce

TRACE = os.path.join(cells.ROOT, "testdata",
                     "gpt2l_silo_fused_v5e_30ms.textproto")
# the readers of the cell the trace was recorded in; a reader that a later
# cell brings for itself comes with a test of its own
PER_LAYER = [m["name"] for m in cells.manifest()["per_layer"]
             if "gpt2l_silo_fused" in m.get("workloads", ["gpt2l_silo_fused"])]


@pytest.fixture(scope="module")
def ctx():
    cell = cells.load_cell("gpt2l_silo_fused")
    call = (0.0, 1.0, 1, {"count": np.array([128 * 1024.0])})
    return trace_reduce.Context(
        summary=trace_reduce.reduce_trace(TRACE), cell=cell,
        session=types.SimpleNamespace(padded_samples_per_round=lambda: 128),
        calls=[call, call], device_kind="TPU v5 lite")


@pytest.mark.parametrize("name", PER_LAYER)
def test_reader_gives_a_finite_number_or_nothing(ctx, name):
    value = cells.load_layer_metric(name).read(ctx)
    assert value is None or np.isfinite(value)


def test_pinned_readings(ctx):
    read = lambda n: cells.load_layer_metric(n).read(ctx)  # noqa: E731
    assert read("device_idle_pct") == pytest.approx(27.391907546666516)
    assert read("host_gap_pct") == read("device_idle_pct")  # one chip
    assert read("collective_pct") == 0
    tokens = 2 * 128 * 1024
    per_token = sum(flops.train_flops_per_unit(ctx.cell.config).values())
    assert read("step_mfu_pct") == pytest.approx(
        100 * per_token * tokens / ctx.summary.busy_s / 197e12)


def test_a_reader_with_nothing_to_read_returns_nothing(ctx):
    # no op of the class in the trace: no roofline share, not a zero
    bare = dataclasses.replace(ctx, summary=copy.deepcopy(ctx.summary))
    for dev in bare.summary.devices:
        for op in dev.ops:
            if op.klass == "matmul":
                op.klass = "other"
    assert cells.load_layer_metric("matmul_roofline").read(bare) is None
