"""Observability layer (ISSUE 1): telemetry registry, span lifecycle,
comm counters on a real inproc exchange, compile tracking, and the
trace_summary CLI over a produced metrics.jsonl."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from fedml_tpu.core.metrics import MetricsLogger
from fedml_tpu.obs.telemetry import (
    Histogram,
    Telemetry,
    metric_key,
    parse_metric_key,
)

REPO = Path(__file__).resolve().parents[1]


# --- histogram bucketing edge cases -----------------------------------------

def test_histogram_log2_buckets_and_stats():
    h = Histogram()
    for v in (0.3, 0.6, 3.0, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(8.9)
    assert snap["min"] == pytest.approx(0.3)
    assert snap["max"] == pytest.approx(5.0)
    # 0.3→le 0.5, 0.6→le 1, 3.0→le 4, 5.0→le 8
    assert snap["buckets"] == {"0.5": 1, "1.0": 1, "4.0": 1, "8.0": 1}


def test_histogram_zero_gets_own_bucket():
    h = Histogram()
    h.observe(0.0)
    h.observe(0.0)
    assert h.buckets == {0.0: 2}
    assert h.count == 2 and h.min == 0.0


def test_histogram_rejects_nan_inf_negative():
    h = Histogram()
    for bad in (float("nan"), float("inf"), float("-inf"), -1.0):
        with pytest.raises(ValueError):
            h.observe(bad)
    assert h.count == 0  # rejected observations leave no partial state


def test_exact_power_of_two_lands_in_own_bucket():
    h = Histogram()
    h.observe(4.0)  # ceil(log2(4)) = 2 → le 4.0, not 8.0
    assert h.buckets == {4.0: 1}


# --- metric key naming convention -------------------------------------------

def test_metric_key_sorted_labels_roundtrip():
    key = metric_key("comm.sent_bytes", {"msg_type": "S2C_SYNC_MODEL"})
    assert key == "comm.sent_bytes{msg_type=S2C_SYNC_MODEL}"
    name, labels = parse_metric_key(key)
    assert name == "comm.sent_bytes" and labels == {"msg_type": "S2C_SYNC_MODEL"}
    # label order must not matter (sorted)
    assert metric_key("x", {"b": 1, "a": 2}) == metric_key("x", {"a": 2, "b": 1}).replace(
        "{a=2,b=1}", "{a=2,b=1}"
    )
    assert metric_key("x", {"b": 1, "a": 2}) == "x{a=2,b=1}"
    assert parse_metric_key("plain") == ("plain", {})


def test_telemetry_counters_gauges_snapshot():
    t = Telemetry()
    t.inc("c.n", 2, kind="a")
    t.inc("c.n", 3, kind="a")
    t.gauge_max("g.peak", 10)
    t.gauge_max("g.peak", 7)  # high-water: keeps the max
    t.observe("h.lat", 0.5)
    snap = t.snapshot()
    assert snap["counters"]["c.n{kind=a}"] == 5
    assert snap["gauges"]["g.peak"] == 10
    assert snap["hists"]["h.lat"]["count"] == 1
    t.reset()
    assert t.snapshot() == {"counters": {}, "gauges": {}, "hists": {}}


# --- span lifecycle ----------------------------------------------------------

def test_span_accumulates_across_repeats_and_nesting():
    t = Telemetry()
    m = MetricsLogger(telemetry=t)
    with m.span("pack"):
        pass
    with m.span("pack"):  # repeated: accumulates until popped
        with m.span("round"):  # nested different-name spans coexist
            pass
    assert set(m.spans) == {"pack", "round"}
    spans = m.pop_spans()
    assert set(spans) == {"time_pack", "time_round"}
    assert spans["time_pack"] >= spans["time_round"]  # outer ⊇ inner
    assert m.pop_spans() == {}  # popped clears
    # every individual span also landed in the telemetry histogram
    assert t.snapshot()["hists"]["span.pack_s"]["count"] == 2


def test_span_recorded_on_exception_path():
    m = MetricsLogger(telemetry=Telemetry())
    with pytest.raises(RuntimeError):
        with m.span("round"):
            raise RuntimeError("boom")
    assert "round" in m.spans  # finally-path accumulation


# --- MetricsLogger lifecycle (satellite: context manager, idempotent close) --

def test_metrics_logger_context_manager_closes_on_exception(tmp_path):
    with pytest.raises(RuntimeError):
        with MetricsLogger(run_dir=str(tmp_path), telemetry=Telemetry()) as m:
            m.log({"loss": 1.0}, step=0)
            raise RuntimeError("crash mid-run")
    assert m._fh is None  # closed on the exception path
    m.close()  # idempotent: second close is a no-op
    lines = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert lines and lines[0]["loss"] == 1.0  # the crashed run is readable


def test_jsonl_schema_roundtrip_with_telemetry_snapshot(tmp_path):
    t = Telemetry()
    with MetricsLogger(run_dir=str(tmp_path), telemetry=t) as m:
        t.inc("comm.sent_bytes", 1024, msg_type="X")
        t.observe("comm.send_latency_s", 0.25, msg_type="X")
        t.event("compile", fn="round_fn", seconds=1.5)
        m.log({"loss": 0.5}, step=7)
        m.log_telemetry()
    lines = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    kinds = [l.get("kind") for l in lines]
    assert kinds == [None, "compile", "telemetry"]  # events drain before snapshot
    assert lines[0]["round"] == 7
    snap = lines[2]
    assert snap["counters"]["comm.sent_bytes{msg_type=X}"] == 1024
    hist = snap["hists"]["comm.send_latency_s{msg_type=X}"]
    assert hist["count"] == 1 and hist["buckets"] == {"0.25": 1}


# --- comm counters on an inproc echo exchange --------------------------------

def test_inproc_echo_records_comm_counters():
    from fedml_tpu.comm.inproc import InprocBus
    from fedml_tpu.comm.message import Message
    from fedml_tpu.obs.telemetry import get_telemetry

    t = get_telemetry()
    base_sent = t.counter_value("comm.sent_msgs", msg_type="OBS_ECHO")
    base_bytes = t.counter_value("comm.sent_bytes", msg_type="OBS_ECHO")

    bus = InprocBus()
    a, b = bus.register(0), bus.register(1)

    class Echo:
        def receive_message(self, mt, msg):
            if msg.receiver == 1:  # echo back once
                reply = Message("OBS_ECHO", 1, 0)
                reply.add_params("payload", msg.get("payload"))
                b.send_message(reply)

    class Sink:
        def receive_message(self, mt, msg):
            pass

    b.add_observer(Echo())
    a.add_observer(Sink())
    m = Message("OBS_ECHO", 0, 1)
    m.add_params("payload", np.ones((64, 64), np.float32))
    a.send_message(m)
    assert bus.drain() == 2  # request + echo

    sent = t.counter_value("comm.sent_msgs", msg_type="OBS_ECHO") - base_sent
    nbytes = t.counter_value("comm.sent_bytes", msg_type="OBS_ECHO") - base_bytes
    recv = t.counter_value("comm.recv_msgs", msg_type="OBS_ECHO")
    assert sent == 2 and recv >= 2
    # 64x64 f32 = 16 KiB raw → > 20 KiB per message on the b64 wire, x2
    assert nbytes > 2 * 16384
    lat = t.snapshot()["hists"].get("comm.send_latency_s{msg_type=OBS_ECHO}")
    assert lat and lat["count"] >= 2


# --- compile tracking --------------------------------------------------------

def test_instrument_jit_counts_signatures_not_calls():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.obs.jax_hooks import instrument_jit

    t = Telemetry()
    f = instrument_jit(jax.jit(lambda x: x * 2), "f", telemetry=t)
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))  # warm: same signature, no new event
    assert t.counter_value("jax.compiles", fn="f") == 1
    f(jnp.ones((8,)))  # new shape → recompile
    assert t.counter_value("jax.compiles", fn="f") == 2
    events = t.drain_events()
    assert [e["kind"] for e in events] == ["compile", "compile"]
    assert all(e["seconds"] >= 0 for e in events)
    # varying python scalars must NOT read as recompiles: jit weak-types
    # a plain float to one dtype regardless of value
    g = instrument_jit(jax.jit(lambda x, s: x * s), "g", telemetry=t)
    for s in (1.0, 2.0, 3.0):
        g(jnp.ones((4,)), s)
    assert t.counter_value("jax.compiles", fn="g") == 1


def test_record_device_memory_none_guarded():
    from fedml_tpu.obs.jax_hooks import record_device_memory

    # CPU devices may or may not implement memory_stats — the call must
    # never raise either way
    record_device_memory(Telemetry())


# --- the round's stages as named scopes (ISSUE 24) ---------------------------

def _lower_round(case):
    """A toy round lowered, never compiled or run: the scopes are debug
    info of the lowered module."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState, make_multi_round_fn
    from fedml_tpu.compress import get_codec
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.linear import logistic_regression
    from fedml_tpu.parallel.spmd import make_client_mesh, make_spmd_round_fn

    bundle = logistic_regression(8, 2)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1),
                           epochs=1, compute_dtype=jnp.bfloat16)
    scale = lambda tree: jax.tree_util.tree_map(lambda l: l * 0.5, tree)
    if case == "spmd":
        fn = make_spmd_round_fn(make_client_mesh(1), lu)
    elif case == "codec_transform":
        fn = jax.jit(make_multi_round_fn(
            lu, 1, codec=get_codec("int8"),
            aggregate_transform=lambda old, stacked, w, rngs: scale(stacked),
            server_update=lambda old, agg, opt: (scale(agg), opt)))
    else:
        kw = {"vmap": {"client_axis_impl": "vmap"},
              "unroll": {"client_unroll": 2}}.get(case, {})
        fn = jax.jit(make_multi_round_fn(lu, 2, **kw))
    key = jax.random.PRNGKey(0)
    state = ServerState(bundle.init(key), (), jnp.zeros((), jnp.int32), key)
    k, steps, b = 2, 2, 4
    shape = jax.ShapeDtypeStruct
    return fn.lower(
        state, shape((k, steps, b, 8), jnp.float32),
        shape((k, steps, b), jnp.int32), shape((k, steps, b), jnp.float32),
        shape((k,), jnp.float32), shape((k,), jnp.float32),
        shape((k,), jnp.int32))


# no codec, no transform; the default server update passes the mean on and
# has no op to name
_PLAIN_ROUND_LACKS = {"fed.codec", "fed.agg_transform", "fed.server_update"}


@pytest.mark.parametrize("case,absent", [
    ("fused", _PLAIN_ROUND_LACKS),
    ("vmap", _PLAIN_ROUND_LACKS),
    ("unroll", _PLAIN_ROUND_LACKS),
    ("spmd", _PLAIN_ROUND_LACKS | {"fed.rounds"}),
    ("codec_transform", set()),
])
def test_round_stages_are_named_scopes_in_debug_info_only(case, absent):
    import re

    from fedml_tpu.obs.scopes import SCOPES

    lowered = _lower_round(case)
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"fed\.[a-z_]+", text)) == set(SCOPES) - absent
    # inside value_and_grad JAX wraps the scope: forward, then backward
    assert "jvp(fed.model)" in text and "transpose(jvp(fed.model))" in text
    assert "transpose(jvp(fed.loss))" in text
    # a scope is debug info, which the compile cache's key strips: the
    # program is the one the cache held before the stages had names
    assert "fed." not in lowered.as_text()


# --- where the compile cache goes, and which device a run is on --------------

def test_compile_cache_env_wins_else_fixed_path_in_checkout(monkeypatch):
    import jax

    from fedml_tpu.utils import compile_cache as cc

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    # set from outside: the directory is left alone, no other is set in code
    monkeypatch.setenv(cc.ENV_VAR, "/some/dir")
    assert cc.configure_compile_cache(0.0) == "/some/dir"
    assert updates == [("jax_persistent_cache_min_compile_time_secs", 0.0)]
    # not set: one fixed path inside the checkout
    del updates[:]
    monkeypatch.delenv(cc.ENV_VAR)
    fixed = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert cc.configure_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed),
                       ("jax_persistent_cache_min_compile_time_secs", 2.0)]


def test_require_tpu_raises_on_cpu_and_report_names_the_device():
    from fedml_tpu.utils.device import device_report, require_tpu

    with pytest.raises(RuntimeError, match="platform='cpu'"):
        require_tpu()
    report = device_report()
    assert report["platform"] == "cpu" and report["device_count"] >= 8
    assert set(report) == {"platform", "device_kind", "device_count"}


def test_bench_peak_table_raises_on_unknown_device_kind():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        import bench
    finally:
        sys.path.pop(0)
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no bf16 peak recorded"):
        bench.peak_bf16_flops("cpu")


def test_chip_smoke_without_a_chip_fails_and_names_the_platform():
    """No chip, no rehearsal argument: non-zero exit, the platform it
    found in the message, and no result line."""
    repo = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(repo / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "found platform=cpu" in out.stderr + out.stdout
    assert '"ok"' not in out.stdout and "leg resnet56" not in out.stdout


# --- end-to-end: simulation emits, trace_summary reads -----------------------

def _tiny_sim(tmp_path, telemetry):
    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models.linear import logistic_regression

    ds = synthetic_classification(num_train=60, num_test=20, input_shape=(8,),
                                  num_classes=2, num_clients=3,
                                  partition="homo", seed=0)
    logger = MetricsLogger(run_dir=str(tmp_path), telemetry=telemetry)
    sim = FedAvgSimulation(
        logistic_regression(8, 2), ds,
        FedAvgConfig(num_clients=3, clients_per_round=3, comm_rounds=2,
                     epochs=1, batch_size=8, frequency_of_the_test=5),
        metrics=logger,
    )
    return sim, logger


def test_simulation_emits_spans_comm_and_compiles(tmp_path):
    t = Telemetry()
    sim, logger = _tiny_sim(tmp_path, t)
    with logger:
        sim.run()
        logger.log_telemetry()
    lines = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    rounds = [l for l in lines if "round" in l and "kind" not in l]
    assert len(rounds) == 2
    assert all("time_round" in r and "time_sample" in r and "time_pack" in r
               for r in rounds)
    assert "time_eval" in rounds[-1]  # final round evaluates
    compiles = [l for l in lines if l.get("kind") == "compile"]
    assert any(c["fn"] == "round_fn" for c in compiles)
    snap = [l for l in lines if l.get("kind") == "telemetry"][-1]
    sent = snap["counters"].get(
        "comm.sent_bytes{msg_type=S2C_SYNC_MODEL}", 0)
    # 3 clients x 2 rounds x model bytes — nonzero logical comm volume
    assert sent > 0
    assert snap["counters"]["jax.compiles{fn=round_fn}"] == 1  # no storm


def test_trace_summary_cli_renders_and_json_parses(tmp_path):
    t = Telemetry()
    sim, logger = _tiny_sim(tmp_path, t)
    with logger:
        sim.run()
        logger.log_telemetry()
    script = str(REPO / "tools" / "trace_summary.py")
    out = subprocess.run([sys.executable, script, str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "per-round spans" in out.stdout
    assert "S2C_SYNC_MODEL" in out.stdout
    assert "compile" in out.stdout

    out = subprocess.run([sys.executable, script, "--json", str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    parsed = json.loads(out.stdout)  # machine-parseable, strict JSON
    s = parsed[str(tmp_path)]
    assert s["num_rounds"] == 2
    assert s["comm"]["S2C_SYNC_MODEL"]["sent_bytes"] > 0
    assert any(c["fn"] == "round_fn" for c in s["compiles"])
    assert "time_round" in s["spans"]


def test_trace_summary_cli_missing_input_exits_nonzero(tmp_path):
    script = str(REPO / "tools" / "trace_summary.py")
    out = subprocess.run(
        [sys.executable, script, str(tmp_path / "does_not_exist")],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 2


def test_trace_default_dir_from_logger_run_dir(tmp_path):
    """Satellite: trace() must not hardcode /tmp when the logger has a
    run_dir, and must log the trace path into the metrics stream."""
    from fedml_tpu.core.metrics import trace

    with MetricsLogger(run_dir=str(tmp_path), telemetry=Telemetry()) as m:
        with trace(logger=m) as tdir:
            assert tdir == os.path.join(str(tmp_path), "trace")
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert any(r.get("kind") == "trace" and r.get("trace_dir") == tdir
               for r in recs)
    assert os.path.isdir(tdir)  # the profiler actually wrote there


# --- distributed trace context (ISSUE 6) ------------------------------------

from fedml_tpu.obs import trace_ctx  # noqa: E402


def test_clock_offset_estimator_synthetic_skew():
    """Pure-function NTP estimator: the min-RTT sample's midpoint wins,
    so a symmetric tight ping recovers a synthetic skew exactly even
    when noisier asymmetric samples surround it."""
    skew = 41.7  # hub monotonic clock = local + skew
    samples = [
        (11.0, 11.015 + skew, 11.020),   # asymmetric, 20 ms RTT: loses
        (10.0, 10.0005 + skew, 10.001),  # symmetric 1 ms RTT: wins
        (12.0, None, 12.001),            # unusable reply
        (13.002, 13.0 + skew, 13.001),   # negative RTT: skipped
    ]
    off, rtt = trace_ctx.estimate_offset(samples)
    assert rtt == pytest.approx(0.001)
    # error bound is rtt/2 by construction; this sample is symmetric so
    # the estimate is exact up to float noise
    assert off == pytest.approx(skew, abs=1e-9)
    assert trace_ctx.estimate_offset([]) == (None, None)
    assert trace_ctx.estimate_offset([(1.0, None, 1.1)]) == (None, None)


def test_trace_ctx_stamps_are_copy_on_write():
    """Stamping forks the hop list: on inproc the SAME params objects
    are shared between sender/receiver/duplicate copies, so an in-place
    append would alias every copy's chain."""
    trace_ctx.set_enabled(True)
    try:
        ctx = trace_ctx.new_ctx(3, round_idx=2)
        assert ctx["hops"] == [] and ctx["rnd"] == 2 and "t0" in ctx
        a = trace_ctx.stamp_ctx(ctx, 3, "send")
        b = trace_ctx.stamp_ctx(ctx, "hub", "hub_in")
        assert ctx["hops"] == []  # base never mutated
        assert [h[:2] for h in a["hops"]] == [[3, "send"]]
        assert [h[:2] for h in b["hops"]] == [["hub", "hub_in"]]
    finally:
        trace_ctx.set_enabled(None)


def test_restamp_parts_reuses_payload_buffers_and_memo():
    """The zero-copy contract under stamping: restamp_parts re-encodes
    ONLY the header line — payload buffers are the same objects by
    identity, the memoized list is never mutated, and an untraced
    message passes through without any JSON work."""
    from fedml_tpu.comm.message import Message

    trace_ctx.set_enabled(True)
    try:
        m = Message("T", 1, 0)
        m.add_params("w", np.arange(4096, dtype=np.float32))
        trace_ctx.ensure(m, 1)
        parts = m.to_frame_parts()
        stamped = trace_ctx.restamp_parts(m, parts, 1, "send")
        assert stamped is not parts
        assert all(s is p for s, p in zip(stamped[1:], parts[1:]))
        assert m.to_frame_parts() is parts  # memo untouched
        hdr = json.loads(bytes(stamped[0]))
        assert [h[:2] for h in hdr[trace_ctx.TRACE_KEY]["hops"]] \
            == [[1, "send"]]
        # the memoized header still carries the UNstamped ctx
        assert json.loads(bytes(parts[0]))[trace_ctx.TRACE_KEY]["hops"] == []
        plain = Message("T", 1, 0)
        plain.add_params("w", np.arange(8, dtype=np.float32))
        pp = plain.to_frame_parts()
        assert trace_ctx.restamp_parts(plain, pp, 1, "send") is pp
    finally:
        trace_ctx.set_enabled(None)


def test_trace_disabled_attaches_nothing():
    from fedml_tpu.comm.message import Message

    trace_ctx.set_enabled(False)
    try:
        m = Message("T", 1, 0)
        trace_ctx.ensure(m, 1)
        assert trace_ctx.TRACE_KEY not in m.params
        # stamping helpers are no-ops without a ctx
        trace_ctx.stamp_msg(m, 1, "send")
        trace_ctx.on_recv(m, 1)
        assert trace_ctx.TRACE_KEY not in m.params
        assert trace_ctx.fork_copy(m) is m
    finally:
        trace_ctx.set_enabled(None)


def test_fed_timeline_stripe_and_pipeline_phases(tmp_path):
    """tools/fed_timeline on synthetic per-process records: the striped
    fan-out's reasm hop splits bcast_deliver/stripe_reasm, the
    round_close pipeline fields surface as decode_wait (subtracted from
    decode_fold) + encode_overlap, and the cohort delivery skew is one
    number."""
    import json as _json
    import sys as _sys

    _sys.path.insert(0, "tools")
    import fed_timeline

    def w(name, recs):
        with open(tmp_path / name, "w") as fh:
            for r in recs:
                fh.write(_json.dumps(r) + "\n")

    # hub clock == node clocks (offset 0) for arithmetic transparency
    sync_hops = lambda node, recv_t: {
        "kind": "trace_hop", "rid": "r", "seq": node, "copy": 0, "org": 0,
        "round": 0, "msg_type": "S2C_SYNC_MODEL", "node": node, "t0": 0.0,
        "hops": [[0, "send", 0.010], ["hub", "hub_in", 0.020],
                 ["hub", "hub_out", 0.030], [node, "reasm", 0.040],
                 [node, "recv", 0.060 + 0.010 * node],
                 [node, "done", 0.200]],
    }
    upload = {
        "kind": "trace_hop", "rid": "r", "seq": 9, "copy": 0, "org": 1,
        "round": 0, "msg_type": "C2S_SEND_MODEL", "node": 0, "t0": 0.200,
        "hops": [[1, "send", 0.210], ["hub", "hub_in", 0.220],
                 ["hub", "hub_out", 0.230], [0, "recv", 0.240],
                 [0, "done", 0.260]],
    }
    close = {"kind": "round_close", "round": 0, "participants": 2,
             "time_agg": 0.001, "t_open_m": 0.0, "t_close_m": 0.252,
             "decode_wait_s": 0.004, "decode_s": 0.005,
             "encode_overlap_s": 0.015}
    w("metrics-node0.jsonl", [sync_hops(1, 0), sync_hops(2, 0), upload,
                              close])
    bundle = fed_timeline.load_run(str(tmp_path))
    rows = fed_timeline.build_rounds(bundle)
    assert len(rows) == 1
    r = rows[0]
    assert abs(r["bcast_deliver"] - 0.010) < 1e-9   # hub_out -> reasm
    assert abs(r["stripe_reasm"] - 0.030) < 1e-9    # reasm -> recv (node 1)
    assert abs(r["decode_wait"] - 0.004) < 1e-9
    # decode_fold = recv->close - normalize - decode_wait
    assert abs(r["decode_fold"] - (0.252 - 0.240 - 0.001 - 0.004)) < 1e-9
    assert abs(r["encode_overlap"] - 0.015) < 1e-9
    # skew across the two receivers' recv stamps: 0.080 - 0.070
    assert abs(r["bcast_skew"] - 0.010) < 1e-9
    summary = fed_timeline.summarize(rows)
    assert summary["p50_extra_s"]["bcast_skew"] is not None
    # critical-path phases never double-count: accounted <= wall
    assert r["accounted_s"] <= r["wall_s"] + 1e-9
