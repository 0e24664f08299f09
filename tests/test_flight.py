"""Flight recorder + postmortem forensics (ISSUE 16): per-category ring
recording, atomic trigger dumps, telemetry taps, and the
fed_forensics attribution tree over synthetic and real bundles —
plus the trace_summary --json transport section via the CLI path."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from fedml_tpu.obs import flight
from fedml_tpu.obs.flight import FlightRecorder
from fedml_tpu.obs.telemetry import Telemetry, get_telemetry

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

import fed_forensics  # noqa: E402


# --- recorder unit ----------------------------------------------------------

def _fresh(tmp_path, tag="nodeX", **kw) -> FlightRecorder:
    r = FlightRecorder(**kw)
    r.configure(str(tmp_path), tag)
    return r


def test_rings_record_and_dump_is_parseable_and_atomic(tmp_path):
    r = _fresh(tmp_path)
    r.record("comm", "send", msg_type="X", nbytes=10)
    r.record("faults", "decision", direction="send", actions=["drop"],
             round=2)
    r.record("events", "round_close", round=0)
    path = r.dump("manual", reason="unit")
    assert path == str(tmp_path / "flight-nodeX.json")
    b = json.loads(Path(path).read_text())
    assert b["schema"] == 1 and b["node"] == "nodeX"
    assert b["trigger"]["kind"] == "manual"
    assert b["history"][-1]["reason"] == "unit"
    comm = b["rings"]["comm"]
    assert comm[-1]["kind"] == "send" and comm[-1]["nbytes"] == 10
    assert b["rings"]["faults"][-1]["actions"] == ["drop"]
    assert "counters" in b["telemetry"]
    # atomic write contract: no temp files survive a completed dump
    assert not list(tmp_path.glob(".flight-*"))


def test_ring_depth_is_bounded(tmp_path):
    r = _fresh(tmp_path, depths={"comm": 8})
    for i in range(50):
        r.record("comm", "send", seq=i)
    b = json.loads(Path(r.dump("manual")).read_text())
    seqs = [row["seq"] for row in b["rings"]["comm"]]
    assert seqs == list(range(42, 50))  # oldest evicted, newest kept


def test_window_excludes_stale_rows(tmp_path):
    r = _fresh(tmp_path, window_s=0.05)
    r.record("comm", "send", age="old")
    time.sleep(0.12)
    r.record("comm", "send", age="new")
    b = json.loads(Path(r.dump("manual")).read_text())
    assert [row["age"] for row in b["rings"]["comm"]] == ["new"]


def test_dump_rate_limited_per_kind_and_force_overrides(tmp_path):
    r = _fresh(tmp_path)
    assert r.dump("reject") is not None
    assert r.dump("reject") is None             # same kind, inside window
    assert r.dump("conn_death") is not None     # other kinds unaffected
    assert r.dump("reject", force=True) is not None


def test_recording_site_cannot_mask_row_stamp_or_kind(tmp_path):
    # a tap-fed field dict carrying "t_m"/"kind" keys (e.g. an event
    # whose payload reuses those names) must not mask the row's own
    # stamp and kind at dump time
    r = _fresh(tmp_path)
    r._rings["notes"].append(
        (time.perf_counter(), "real_kind", {"kind": "evil", "t_m": -1.0}))
    row = json.loads(Path(r.dump("manual")).read_text())["rings"]["notes"][-1]
    assert row["kind"] == "real_kind" and row["t_m"] > 0


def test_no_run_dir_records_history_but_writes_nothing(tmp_path):
    r = FlightRecorder()
    r.configure(None, "lib")
    assert r.dump("exception", reason="boom") is None
    assert r._history[-1]["kind"] == "exception"
    assert not list(tmp_path.iterdir())


def test_env_kill_switch_disables_recording(tmp_path, monkeypatch):
    monkeypatch.setenv("FEDML_TPU_FLIGHT", "0")
    r = FlightRecorder()
    r.configure(str(tmp_path), "off")
    r.record("comm", "send")
    assert not r.enabled or True  # enabled captured at construction
    assert r.dump("manual") is None
    assert not list(tmp_path.glob("flight-*.json"))


def test_telemetry_taps_route_events_spans_and_pin_clock_sync(tmp_path):
    tel = Telemetry()
    r = _fresh(tmp_path, tag="tapped")
    tel.set_event_tap(r._on_event)
    tel.set_observe_tap(r._on_observe)
    tel.event("clock_sync", node=7, offset_s=0.25)
    tel.event("round_close", round=3)
    tel.event("trace_hop", seq=1, hops=[[7, "send", 1.0]])
    tel.observe("span.fold_s", 0.5)
    tel.observe("other.hist_s", 9.9)  # non-span: must NOT hit the ring
    b = json.loads(Path(r.dump("manual")).read_text())
    assert b["clock_sync"]["offset_s"] == 0.25  # pinned, eviction-proof
    assert any(row["kind"] == "round_close" and row["round"] == 3
               for row in b["rings"]["events"])
    assert any(row["kind"] == "trace_hop" for row in b["rings"]["hops"])
    spans = b["rings"]["spans"]
    assert [s["kind"] for s in spans] == ["span.fold_s"]
    assert spans[0]["v"] == 0.5


def test_excepthook_dumps_before_original_hook(tmp_path):
    r = _fresh(tmp_path, tag="hooked")
    prev = sys.excepthook
    seen = []
    sys.excepthook = lambda *a: seen.append(a)
    try:
        r.install_excepthooks()
        try:
            raise ValueError("boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        assert seen, "original hook must still run"
        b = json.loads((tmp_path / "flight-hooked.json").read_text())
        assert b["trigger"]["kind"] == "exception"
        assert "boom" in b["trigger"]["reason"]
    finally:
        sys.excepthook = prev


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform without SIGUSR2")
def test_sigusr2_snapshots_a_live_process(tmp_path):
    r = _fresh(tmp_path, tag="live")
    prev = signal.getsignal(signal.SIGUSR2)
    try:
        r.install_signal_handlers()
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        p = tmp_path / "flight-live.json"
        while not p.exists() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert json.loads(p.read_text())["trigger"]["kind"] == "sigusr2"
    finally:
        signal.signal(signal.SIGUSR2, prev)


def test_module_note_and_trigger_reach_global_recorder(tmp_path):
    r = flight.get_recorder()
    old_dir, old_node = r.run_dir, r.node
    try:
        r.configure(str(tmp_path), "glob")
        flight.note("notes", "marker", tag="here")
        path = flight.trigger("manual", reason="module-level", force=True)
        rows = json.loads(Path(path).read_text())["rings"]["notes"]
        assert any(row["kind"] == "marker" for row in rows)
    finally:
        r.configure(old_dir, old_node or "proc")


def test_flight_metrics_are_schema_registered():
    from fedml_tpu.obs.metric_schema import EVENTS, metric_type
    for name in ("flight.dumps", "flight.dumps_suppressed",
                 "flight.dump_errors"):
        assert metric_type(name) == "counter", name
    assert metric_type("flight.dump_write_s") == "histogram"
    assert "flight_dump" in EVENTS


# --- forensics over synthetic bundles ---------------------------------------

def _write_bundle(run_dir, tag, *, history=(), rings=None, counters=None,
                  clock_sync=None, t0=1000.0):
    b = {
        "schema": 1, "node": tag, "pid": 1, "window_s": 60.0,
        "trigger": (history[-1] if history
                    else {"kind": "manual", "reason": "", "round": None,
                          "t_m": t0, "t_wall": t0}),
        "history": list(history),
        "clock_sync": clock_sync,
        # identical anchors across tags: wall-mode mapping is identity,
        # so synthetic t_m values line up directly
        "t_m_dump": t0 + 100.0, "t_wall_dump": t0 + 100.0,
        "telemetry": {"counters": counters or {}, "gauges": {},
                      "hists": {}},
        "rings": dict({"events": [], "hops": [], "spans": [], "comm": [],
                       "faults": [], "locks": [], "notes": []},
                      **(rings or {})),
    }
    Path(run_dir, f"flight-{tag}.json").write_text(json.dumps(b))
    return b


def _server_rounds(t0=1000.0, walls=(2.0, 2.0, 2.0)):
    """round_close events ring rows for rounds 0..len(walls)-1."""
    rows, t = [], t0
    for i, w in enumerate(walls):
        rows.append({"t_m": t + w, "kind": "round_close", "round": i,
                     "t_open_m": t, "t_close_m": t + w, "participants": 3})
        t += w
    return rows


def test_forensics_names_client_crash_and_its_round(tmp_path):
    _write_bundle(tmp_path, "node0",
                  rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node2", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}])
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "client_crash"
    assert v["fault_round"] == 1
    assert v["confidence"] == "high"
    assert v["evidence"][0]["source"] == "node2"


def test_forensics_muxer_crash_vs_shm_peer_crash(tmp_path):
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "mux1", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}])
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] \
        == "muxer_crash"
    # same crash, but the muxer was pushing frames over an shm lane
    _write_bundle(tmp_path, "mux1", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}],
        counters={"comm.shm_frames{msg_type=C2S_SEND_MODEL}": 6.0})
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] \
        == "shm_peer_crash"


def test_forensics_distinguishes_drop_kinds_by_msg_type(tmp_path):
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node1",
                  counters={"faults.injected{action=drop,"
                            "msg_type=C2S_SEND_MODEL}": 4.0},
                  rings={"faults": [
                      {"t_m": 1000.5, "kind": "decision",
                       "direction": "send", "msg_type": "C2S_SEND_MODEL",
                       "round": 0, "actions": ["drop"]}]})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "message_drop" and v["fault_round"] == 0
    # the SAME action on digest frames only is a stats-plane blackout
    os.unlink(tmp_path / "flight-node1.json")
    _write_bundle(tmp_path, "node1",
                  counters={"faults.injected{action=drop,"
                            "msg_type=C2S_TELEMETRY}": 4.0})
    _write_bundle(tmp_path, "node0", history=[
        {"kind": "slo_violation", "reason": "stats_plane_coverage",
         "round": 1, "t_m": 1003.0, "t_wall": 1003.0}],
        rings={"events": _server_rounds()})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "telemetry_loss" and v["fault_round"] == 1


def test_forensics_byzantine_mux_vs_client_and_stripe(tmp_path):
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node3",
                  counters={"faults.injected{action=scale_grad,"
                            "msg_type=C2S_SEND_MODEL}": 3.0})
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] \
        == "malicious_client"
    _write_bundle(tmp_path, "mux1",
                  counters={"faults.injected{action=sign_flip,"
                            "msg_type=C2S_SEND_MODEL}": 3.0})
    os.unlink(tmp_path / "flight-node3.json")
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] \
        == "malicious_muxer"
    os.unlink(tmp_path / "flight-mux1.json")
    _write_bundle(tmp_path, "node2",
                  counters={"faults.injected{action=drop_stripe,"
                            "msg_type=S2C_SYNC_MODEL}": 3.0})
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] \
        == "stripe_fault"


def test_forensics_hub_restart_from_conn_death_plus_reconnects(tmp_path):
    _write_bundle(tmp_path, "node0",
                  history=[{"kind": "conn_death",
                            "reason": "hub connection lost", "round": None,
                            "t_m": 1003.0, "t_wall": 1003.0}],
                  rings={"events": _server_rounds()},
                  counters={"comm.reconnects": 1.0})
    _write_bundle(tmp_path, "node1",
                  history=[{"kind": "conn_death",
                            "reason": "hub connection lost", "round": None,
                            "t_m": 1003.1, "t_wall": 1003.1}],
                  counters={"comm.reconnects": 1.0})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "hub_restart"
    assert v["fault_round"] == 1  # 1003.0 falls in round 1's interval


def test_forensics_shm_ring_full_and_straggler_and_none(tmp_path):
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node1",
                  counters={"comm.shm_fallbacks{reason=ring_full}": 9.0},
                  rings={"comm": [{"t_m": 1000.2, "kind": "shm_refusal",
                                   "reason": "ring_full", "nbytes": 2<<20}]})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "shm_ring_full" and v["fault_round"] == 0
    os.unlink(tmp_path / "flight-node1.json")
    _write_bundle(tmp_path, "node0", history=[
        {"kind": "deadline_overrun", "reason": "arrived=2", "round": 1,
         "t_m": 1004.0, "t_wall": 1004.0},
        {"kind": "deadline_overrun", "reason": "arrived=2", "round": 2,
         "t_m": 1006.0, "t_wall": 1006.0}],
        rings={"events": _server_rounds()})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "straggler" and v["fault_round"] == 1
    # a healthy run: bundles present, nothing anomalous -> "none"
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] == "none"


def test_forensics_lock_contention_channel(tmp_path):
    """PR-16's lock-wait ring finally feeds a verdict: real recorded
    blocking (CheckedLock tap rows) with nothing else anomalous yields
    a low-confidence lock_contention verdict naming the hottest lock;
    below the wait thresholds it stays "none"; and a crash on record
    SHADOWS it (contention explains latency, it is not the fault)."""
    locks = [{"t_m": 1000.5, "lock": "round_lock", "wait_s": 0.03},
             {"t_m": 1001.0, "lock": "round_lock", "wait_s": 0.04},
             {"t_m": 1001.5, "lock": "TcpHub._lock", "wait_s": 0.0}]
    _write_bundle(tmp_path, "node0",
                  rings={"events": _server_rounds(), "locks": locks})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "lock_contention"
    assert v["confidence"] == "low"
    hot = [e for e in v["evidence"] if e["kind"] == "lock_wait"]
    assert hot and hot[0]["lock"] == "round_lock"
    assert hot[0]["contended"] == 2
    assert any(e["kind"] == "hottest_lock" and e["lock"] == "round_lock"
               for e in v["evidence"])
    # the ranked report is present regardless of the verdict
    assert v["lock_contention"][0]["lock"] == "round_lock"
    # sub-threshold waits (< 50 ms total, < 20 ms max) do not verdict
    tiny = [{"t_m": 1000.5, "lock": "round_lock", "wait_s": 0.001}]
    _write_bundle(tmp_path, "node0",
                  rings={"events": _server_rounds(), "locks": tiny})
    assert fed_forensics.analyze(str(tmp_path))["fault_kind"] == "none"
    # a crash outranks contention: the stall is evidence, not the fault
    _write_bundle(tmp_path, "node0",
                  rings={"events": _server_rounds(), "locks": locks})
    _write_bundle(tmp_path, "node2", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}])
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "client_crash"


def test_forensics_round_diff_flags_the_anomalous_round(tmp_path):
    spans = [{"t_m": 1000.5, "kind": "span.decode_wait_s", "v": 0.01},
             {"t_m": 1003.0, "kind": "span.decode_wait_s", "v": 0.50}]
    _write_bundle(tmp_path, "node0",
                  history=[{"kind": "deadline_overrun", "reason": "",
                            "round": 1, "t_m": 1003.5, "t_wall": 1003.5},
                           {"kind": "deadline_overrun", "reason": "",
                            "round": 2, "t_m": 1005.5, "t_wall": 1005.5}],
                  rings={"events": _server_rounds(), "spans": spans})
    v = fed_forensics.analyze(str(tmp_path))
    d = v["round_diff"]
    assert d["round"] == 1 and d["vs_round"] == 0  # nearest healthy
    row = d["metrics"]["spans_p50.span.decode_wait_s"]
    assert row["anomalous"] == 0.5 and row["healthy"] == 0.01


def test_forensics_empty_dir_and_truncated_bundle(tmp_path):
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "no_bundles"
    (tmp_path / "flight-node9.json").write_text('{"schema": 1, "nod')
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "none"
    assert len(v["bundle_errors"]) == 1  # reported, never fatal


def test_forensics_cli_and_perfetto_export(tmp_path):
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node2", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}])
    script = str(REPO / "tools" / "fed_forensics.py")
    trace_path = tmp_path / "flight.trace.json"
    out = subprocess.run(
        [sys.executable, script, str(tmp_path),
         "--out", str(tmp_path / "verdict.json"),
         "--perfetto", str(trace_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    v = json.loads((tmp_path / "verdict.json").read_text())
    assert json.loads(out.stdout) == v  # stdout is the same strict JSON
    assert v["fault_kind"] == "client_crash"
    trace = json.loads(trace_path.read_text())
    names = {e["name"] for e in trace["traceEvents"]}
    assert "round_close" in names and "trigger:crash" in names
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert len(pids) == 2  # one process track per bundle


# --- trace_summary --json transport section (CLI path) ----------------------

def test_trace_summary_json_transport_section_via_cli(tmp_path):
    """The --json transport block (tcp/shm/delta byte split + fallback
    reasons) through the real CLI over a synthesized metrics.jsonl —
    the shape tools/fed_xport_run.py and the forensics layer consume."""
    recs = [
        {"round": 0, "time_round": 0.5},
        {"round": 1, "time_round": 0.5},
        {"kind": "telemetry", "counters": {
            "comm.sent_bytes{msg_type=S2C_SYNC_MODEL}": 6000.0,
            "comm.recv_bytes{msg_type=C2S_SEND_MODEL}": 4000.0,
            "comm.shm_bytes{msg_type=C2S_SEND_MODEL}": 2500.0,
            "comm.shm_frames{msg_type=C2S_SEND_MODEL}": 5.0,
            "comm.shm_fallbacks{reason=ring_full}": 2.0,
            "comm.shm_fallbacks{reason=too_big}": 1.0,
            "comm.delta_bcast_bytes": 1500.0,
            "comm.delta_full_fallbacks{reason=no_acked_base}": 1.0,
            "comm.delta_resyncs": 1.0,
        }, "gauges": {}, "hists": {}},
    ]
    (tmp_path / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    script = str(REPO / "tools" / "trace_summary.py")
    out = subprocess.run([sys.executable, script, "--json", str(tmp_path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    t = json.loads(out.stdout)[str(tmp_path)]["transport"]
    assert t["wire_bytes_total"] == 10000.0
    assert t["shm_payload_bytes"] == 2500.0
    assert t["shm_share"] == pytest.approx(0.25)
    assert t["tcp_inline_bytes"] == 7500.0
    assert t["shm_frames"] == 5.0
    assert t["shm_fallbacks"] == {"ring_full": 2.0, "too_big": 1.0}
    assert t["delta_bcast_bytes"] == 1500.0
    assert t["delta_full_fallbacks"] == {"no_acked_base": 1.0}
    assert t["delta_resyncs"] == 1.0


# --- end-to-end: a crashed client leaves its black box ----------------------

@pytest.mark.slow
def test_crashed_client_leaves_parseable_bundle_ci_pin(tmp_path):
    """CI artifact contract (ISSUE 16 satellite): a client that
    os._exit()s mid-round must leave a parseable flight bundle whose
    crash trigger names the round, and fed_forensics must attribute
    client_crash from the run_dir's bundles alone."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    rc = launch(num_clients=3, rounds=3, seed=0, batch_size=16,
                out_path=str(tmp_path / "final.npz"),
                run_dir=str(tmp_path), crash_client_at_round=1,
                round_timeout=20.0, env=env, info={}, timeout=240.0)
    assert rc == 0
    bundles = sorted(tmp_path.glob("flight-node*.json"))
    assert bundles, "no flight bundles written"
    crashed = [json.loads(p.read_text()) for p in bundles
               if any(h["kind"] == "crash"
                      for h in json.loads(p.read_text())["history"])]
    assert crashed, "crashed client left no crash-trigger bundle"
    assert crashed[0]["trigger"]["round"] == 1
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "client_crash"
    assert v["fault_round"] == 1


# --- lock-contention probe (CheckedLock wait tap) ----------------------------

def test_lock_wait_tap_records_edge_fold_contention(tmp_path):
    """The CheckedLock tap measures BLOCK time and feeds the flight
    recorder's lock ring: under forced contention the edge hub's fold
    lock shows up with a nonzero wait_s, and fed_forensics ranks it."""
    import threading

    from fedml_tpu.analysis import locks as locks_mod

    r = _fresh(tmp_path, tag="edge5")
    locks_mod.set_enabled(True)
    try:
        locks_mod.set_acquire_tap(r._on_lock)
        # the REAL production lock: a stub-backed manager, so the name
        # asserted below is the one EdgeHubManager actually creates
        from fedml_tpu.algorithms.edge_hub import EdgeHubManager

        class _StubBackend:
            node_id = 5
            node_ids = [5, 6]

            def add_observer(self, obs):
                pass

        mgr = EdgeHubManager(_StubBackend(), _StubBackend(), None, None)
        lock = mgr._fold_lock
        assert isinstance(lock, locks_mod.CheckedLock)
        entered = threading.Event()

        def holder():
            with lock:
                entered.set()
                time.sleep(0.08)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert entered.wait(5)
        with lock:  # blocks behind the holder: a measured, real wait
            pass
        t.join(5)
    finally:
        locks_mod.set_acquire_tap(None)
        locks_mod.set_enabled(None)
    b = json.loads(Path(r.dump("manual", force=True)).read_text())
    rows = [row for row in b["rings"]["locks"]
            if row.get("lock") == "EdgeHubManager._fold_lock"]
    assert rows, "fold-lock acquires never reached the lock-wait ring"
    assert max(float(row.get("wait_s") or 0) for row in rows) >= 0.05
    top = fed_forensics.lock_contention({"edge5": b})
    ent = [e for e in top if e["lock"] == "EdgeHubManager._fold_lock"]
    assert ent, f"fold lock missing from contention ranking: {top}"
    assert ent[0]["contended"] >= 1
    assert ent[0]["wait_max_s"] >= 0.05
    # the holder's own uncontended acquire is GATED out of the ring
    # (below the wait threshold): only the blocked acquire is recorded
    assert ent[0]["acquires"] == 1


def test_lock_wait_ring_threshold_gates_and_feeds_histogram(tmp_path):
    """The lock ring is a contention profile: acquires below the
    ``FEDML_TPU_FLIGHT_LOCK_WAIT_S`` threshold never reach it (they
    would evict the contended rows), waits past it land in the ring AND
    the ``lock.wait_s`` histogram."""
    from fedml_tpu.obs.telemetry import get_telemetry

    r = _fresh(tmp_path, tag="nodeL")
    assert r.lock_wait_s == flight.DEFAULT_LOCK_WAIT_S
    tel = get_telemetry()
    before = sum(h.count for k, h in tel.hists.items()
                 if k.startswith("lock.wait_s"))
    # uncontended-scale wait: gated out of ring and histogram
    r._on_lock("Hub._lock", 1, wait_s=1e-6)
    # contended wait: recorded in both
    r._on_lock("Hub._lock", 1, wait_s=0.05)
    b = json.loads(Path(r.dump("manual", force=True)).read_text())
    rows = [row for row in b["rings"]["locks"]
            if row.get("lock") == "Hub._lock"]
    assert len(rows) == 1 and rows[0]["wait_s"] == 0.05
    after = {k: h for k, h in tel.hists.items()
             if k.startswith("lock.wait_s")}
    assert sum(h.count for h in after.values()) == before + 1
    assert any("lock=Hub._lock" in k for k in after)


def test_lock_wait_threshold_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv(flight.ENV_LOCK_WAIT, "0.5")
    r = _fresh(tmp_path, tag="nodeL2")
    assert r.lock_wait_s == 0.5
    r._on_lock("Hub._lock", 1, wait_s=0.1)  # below the raised bar
    b = json.loads(Path(r.dump("manual", force=True)).read_text())
    assert not [row for row in b["rings"]["locks"]
                if row.get("lock") == "Hub._lock"]
