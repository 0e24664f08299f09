"""``ssm_scan_kernel_pct`` on made-up device ops that carry the program's
scope names: the Pallas custom calls' share of the seconds under
``model.ssm_scan``, 0 where the scan is lax ops alone, nothing where no op
carries the scope."""

import pytest

from benchmark import cells, trace_reduce
from benchmark.tests.test_nemotron_h_readers import (
    BWD, CELL, FWD, MAMBA, OPS, context, op, read,
)

NAME = "ssm_scan_kernel_pct"
SCAN = MAMBA + "model.ssm_scan/"


def kernel(tf_op, seconds):
    return trace_reduce.Op("custom-call.1", 0.0, 0.0, self_ns=seconds * 1e9,
                           klass="other",
                           stats={"tf_op": tf_op,
                                  "hlo_category": "custom-call"})


def test_the_manifest_reads_it_in_the_nemotron_h_cell_only():
    entry = next(m for m in cells.manifest()["per_layer"]
                 if m["name"] == NAME)
    assert (entry["workloads"], entry["moves"], entry["layer"],
            entry["better"], entry["unit"], entry["source"]) == (
        [CELL], "tokens_per_s", "kernels", "higher", "%", "device_trace")
    assert cells.manifest()["per_layer"][-1]["name"] == NAME


def test_the_kernels_share_of_the_scans_seconds():
    ops = [kernel(FWD + SCAN + "vmap(ssd_fwd)/pallas_call", 0.2),
           kernel(BWD + SCAN + "transpose(vmap(ssd_bwd))/pallas_call", 0.5),
           op(FWD + SCAN + "mul", 0.05), op(BWD + SCAN + "copy", 0.05),
           # a custom call outside the scope counts for nothing
           kernel(FWD + "DecoderLM/Block_1/model.moe_experts/pallas_call",
                  0.4)]
    assert read(NAME, context(ops=ops)) == pytest.approx(100 * 0.7 / 0.8)


def test_the_lax_scan_reads_0_and_no_scope_reads_nothing():
    assert read(NAME, context(ops=OPS)) == 0.0
    assert read(NAME, context(ops=[op(FWD + "DecoderLM/add", 1.0)])) is None
