"""The readers the ``nemo3super_silo_agent8k`` cell brought, on made-up
device ops that carry the program's scope names, with a stand-in session and
the counter of the made-up calls; and on the other families' recorded
traces, where each finds nothing."""

import math
import os
import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

CELL = "nemo3super_silo_agent8k"
TESTDATA = os.path.join(cells.ROOT, "testdata")
NEW = ["ssm_pct", "ssm_scan_roofline", "ssm_retention_pct", "moe_latent_pct"]
STEP = "jit(multi_round_fn)/fed.round/fed.step/"
FWD, BWD = STEP + "jvp(fed.model)/", STEP + "transpose(jvp(fed.model))/"
MAMBA = "DecoderLM/Block_0/Mamba2Mixer_0/"
EXPERT = "DecoderLM/Block_1/ExpertLayer_0/"
# the made-up calls: 3 calls of one round of 16 sequences of 8192 tokens
CALLS, SAMPLES, L = 3, 16, 8192
LOG_DECAY = -0.05  # a layer's mean of dt A


def op(tf_op, seconds, klass="other"):
    return trace_reduce.Op("fusion.1", 0.0, 0.0, self_ns=seconds * 1e9,
                           klass=klass, stats={"tf_op": tf_op})


OPS = [
    op(FWD + MAMBA + "model.ssm_proj/in_proj/dot_general", 0.2, "matmul"),
    op(FWD + MAMBA + "model.ssm_proj/logistic", 0.05),
    op(FWD + MAMBA + "model.ssm_scan/vmap(dot_general)", 0.3, "matmul"),
    op(FWD + MAMBA + "model.ssm_scan/while", 0.1, "loop"),
    op(BWD + MAMBA + "model.ssm_scan/transpose(vmap(dot_general))", 0.6,
       "matmul"),
    op(FWD + MAMBA + "model.ssm_out/out_proj/dot_general", 0.1, "matmul"),
    op(BWD + MAMBA + "model.ssm_out/rsqrt", 0.05),
    op(FWD + EXPERT + "model.moe_latent/dot_general", 0.15, "matmul"),
    op(BWD + EXPERT + "model.moe_latent/dot_general", 0.3, "matmul"),
    op(FWD + EXPERT + "model.moe_experts/gmm", 0.4, "other"),
    op(STEP + "fed.optimizer/add", 0.1),
]
BUSY = 4.0


def context(ops=OPS, counters=None, cell=CELL):
    device = trace_reduce.DeviceSummary(
        plane="/device:TPU:0", busy_ns=BUSY * 1e9, class_ns={},
        collective_ns=0.0, collective_exposed_ns=0.0, busy=[], ops=ops)
    summary = trace_reduce.Summary(window_ns=BUSY * 1e9, devices=[device],
                                   host_gaps=[], calls=CALLS)
    metrics = {k: np.array([v]) for k, v in (counters or {}).items()}
    return trace_reduce.Context(
        summary=summary, cell=cells.load_cell(cell),
        session=types.SimpleNamespace(
            padded_samples_per_round=lambda: SAMPLES),
        calls=[(0.0, 0.1, 1, metrics)] * CALLS, device_kind="TPU v5 lite")


def read(name, ctx):
    return cells.load_layer_metric(name).read(ctx)


def test_the_manifest_reads_them_in_this_cell_only():
    by_name = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tokens_per_s"
        assert by_name[name]["unit"] == "%"
    assert [by_name[n]["source"] for n in NEW] == [
        "device_trace", "device_trace", "program_counter", "device_trace"]
    assert [by_name[n]["layer"] for n in NEW] == [
        "model step", "kernels", "model step", "model step"]
    assert [by_name[n]["better"] for n in NEW] == [
        "lower", "higher", "higher", "lower"]
    names = [m["name"] for m in cells.manifest()["per_layer"]]
    assert names[-4:] == NEW
    entry = cells.manifest()["workloads"][-1]
    assert (entry["name"], entry["chips"], entry["traffic"]) == (
        CELL, 1, "silo_agent8k")


def test_the_scopes_and_the_counter_a_reader_names_are_the_programs():
    from fedml_tpu.models.decoder import SSM_LOG_DECAY_MEAN
    from fedml_tpu.obs import scopes

    assert cells.load_layer_metric("ssm_scan_roofline").SCAN \
        == scopes.SSM_SCAN
    assert cells.load_layer_metric("moe_latent_pct").SCOPE \
        == scopes.MOE_LATENT
    assert {scopes.SSM_PROJ, scopes.SSM_SCAN, scopes.SSM_OUT} == {
        s for s in scopes.MODEL_SCOPES
        if s.startswith(cells.load_layer_metric("ssm_pct").SSM)}
    assert cells.load_layer_metric("ssm_retention_pct").COUNTER \
        == SSM_LOG_DECAY_MEAN


def test_the_mixers_share_is_every_op_under_its_three_scopes():
    assert read("ssm_pct", context()) == pytest.approx(
        100 * (0.2 + 0.05 + 0.3 + 0.1 + 0.6 + 0.1 + 0.05) / BUSY)


def test_the_scans_roofline_credits_its_own_work_over_its_seconds():
    tokens = CALLS * SAMPLES * L
    # bytes bound it: 3 passes x 4672 B x 5 layers a token
    least = 3 * 4672 * 5 * tokens / 819e9
    assert least > 3 * 655_360 * 5 * tokens / 197e12
    got = read("ssm_scan_roofline", context())
    assert got == pytest.approx(100 * least / (0.3 + 0.1 + 0.6))
    # the seconds of every op under the scope, whatever its class
    assert 0 < got < 100


def test_the_latent_projections_share_is_their_scopes_seconds():
    assert read("moe_latent_pct", context()) == pytest.approx(
        100 * 0.45 / BUSY)


@pytest.mark.parametrize("log_decay", [LOG_DECAY, 0.0, -2.0])
def test_retention_is_the_geometric_mean_decay_a_layer_step(log_decay):
    # each call: 16 sequences at batch 1 are 16 steps, five Mamba layers each
    ctx = context(counters={"ssm_log_decay_mean": 16 * 5 * log_decay})
    assert read("ssm_retention_pct", ctx) == pytest.approx(
        100 * math.exp(log_decay))


def test_nothing_to_read_without_the_ops_or_the_counter():
    bare = context(ops=[op(FWD + "DecoderLM/Block_0/add", 1.0)])
    for name in ("ssm_pct", "ssm_scan_roofline", "moe_latent_pct",
                 "ssm_retention_pct"):
        assert read(name, bare) is None


@pytest.mark.parametrize("trace, cell", [
    ("gpt2l_silo_fused_v5e_30ms.textproto", "gpt2l_silo_fused"),
    ("mellum2_silo_code8k/v5e_step.textproto", "mellum2_silo_code8k"),
    ("kimilin_silo_doc8k/v5e_turn.textproto", "kimilin_silo_doc8k"),
    ("trinitymini_silo_chat8k/v5e_turn.textproto",
     "trinitymini_silo_chat8k")])
@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_in_a_program_without_the_mechanism(name, trace, cell):
    """The other families' programs (and the parent's): no op under a
    ``model.ssm_*`` or ``model.moe_latent`` scope, no counter.  A reader
    says nothing and does not raise."""
    call = (0.0, 0.1, 1, {"count": np.array([8192.0])})
    other = trace_reduce.Context(
        summary=trace_reduce.reduce_trace(os.path.join(TESTDATA, trace)),
        cell=cells.load_cell(cell),
        session=types.SimpleNamespace(padded_samples_per_round=lambda: 1),
        calls=[call], device_kind="TPU v5 lite")
    assert read(name, other) is None
