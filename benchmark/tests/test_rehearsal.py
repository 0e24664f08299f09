"""The harness end to end on the CPU at the toy geometry, and its refusal to
measure anything there."""

import os
import subprocess
import sys

import pytest

from benchmark import cells

RUN = os.path.join(cells.ROOT, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
WORKLOADS = sorted(f[:-5] for f in os.listdir(
    os.path.join(cells.ROOT, "workloads")))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_rehearsal_runs_every_workload_file_and_prints_no_result(workload):
    out = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "5",
         "--seconds", "1", "--rehearsal"],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines and all(l.startswith("platform=cpu rehearsal") for l in lines)
    assert "correct True" in lines[0]
    assert '"metrics"' not in out.stdout  # no number under a metric's name


def test_without_a_tpu_it_names_the_platform_and_prints_nothing():
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "gpt2l_silo_fused", "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and out.stdout == ""
    assert "found platform='cpu'" in out.stderr
