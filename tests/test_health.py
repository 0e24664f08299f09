"""fedhealth (PR 11): mergeable telemetry digests, the in-band stats
plane, and the federation SLO engine.

The algebra pins mirror the streaming-aggregation ones: ``merge`` must
be associative, commutative, and identity-preserving so muxer-side
pre-merge == hub rollup == flat per-client merge — compared on
``serialize`` BYTES, not dict equality, exactly the way PR 10 pinned
muxed-vs-per-process upload digests.  Test observations use dyadic
rationals (k/1024) so float sums associate exactly.

The federation test drives the REAL process topology (hub + server +
clients + a muxer over sockets) with the stats plane on and asserts the
acceptance shape: digest streams == CONNECTIONS (not clients), a live
``status.json`` + final ``slo_report.json`` in run_dir, and in-band
percentiles within one log2 bucket of the post-hoc exact numbers.
"""

import json
import math
import os
import sys
import time

import pytest

from fedml_tpu.obs import digest as dg
from fedml_tpu.obs.slo import (
    SloEngine,
    SloSpec,
    build_status,
    hist_quantile,
    write_json_atomic,
)
from fedml_tpu.obs.telemetry import Telemetry


def _reg(events=()):
    """A private registry with a few deterministic dyadic observations."""
    t = Telemetry()
    for name, value, labels in events:
        if name.endswith("_s"):
            t.observe(name, value, **labels)
        else:
            t.inc(name, value, **labels)
    return t


def _sample_registries(n=4):
    regs = []
    for i in range(n):
        t = Telemetry()
        for k in range(i + 1):
            t.inc("comm.sent_msgs", 1, msg_type="C2S_SEND_MODEL")
            t.inc("comm.sent_bytes", 1024 * (k + 1),
                  msg_type="C2S_SEND_MODEL")
            t.observe("span.round_s", (k + 1) / 1024.0)
        t.gauge_set("hub.nodes", 10 + i)
        regs.append(t)
    return regs


# --- digest algebra ----------------------------------------------------------


def test_empty_digest_is_merge_identity():
    d = dg.registry_digest(_sample_registries(1)[0], node=1, seq=1, t=5.0)
    assert dg.serialize(dg.merge(d, dg.empty_digest())) == dg.serialize(d)
    assert dg.serialize(dg.merge(dg.empty_digest(), d)) == dg.serialize(d)
    e = dg.merge(dg.empty_digest(), dg.empty_digest())
    assert dg.serialize(e) == dg.serialize(dg.empty_digest())


def test_merge_associative_and_commutative_byte_identical():
    regs = _sample_registries(4)
    ds = [dg.registry_digest(t, node=i + 1, seq=1, t=100.0 + i)
          for i, t in enumerate(regs)]
    a, b, c, d = ds
    forms = [
        dg.merge(dg.merge(dg.merge(a, b), c), d),
        dg.merge(a, dg.merge(b, dg.merge(c, d))),
        dg.merge(dg.merge(d, c), dg.merge(b, a)),
        dg.merge(dg.merge(a, c), dg.merge(d, b)),
        dg.merge_all([d, b, a, c]),
    ]
    blobs = {dg.serialize(f) for f in forms}
    assert len(blobs) == 1, "merge must be order-insensitive to the byte"
    merged = forms[0]
    # counters added exactly across all four registries
    assert merged["counters"]["comm.sent_msgs{msg_type=C2S_SEND_MODEL}"] \
        == 1 + 2 + 3 + 4
    # hist buckets added bucket-wise, count conserved
    h = merged["hists"]["span.round_s"]
    assert h["count"] == 10 and sum(h["buckets"].values()) == 10
    assert merged["nodes"] == [1, 2, 3, 4]


def test_muxer_premerge_equals_flat_merge_pinned():
    """Grouping digests muxer-style (pre-merge per connection, then the
    hub folds group results) must equal the flat per-client fold — the
    digest twin of the muxed-vs-per-process upload pin."""
    regs = _sample_registries(6)
    ds = [dg.registry_digest(t, node=i + 1, seq=1, t=50.0 + i)
          for i, t in enumerate(regs)]
    flat = dg.merge_all(ds)
    # two muxers: clients 1-3 on one connection, 4-6 on the other
    pre_a = dg.merge_all(ds[:3])
    pre_b = dg.merge_all(ds[3:])
    assert dg.serialize(dg.merge(pre_a, pre_b)) == dg.serialize(flat)
    assert dg.serialize(dg.merge(pre_b, pre_a)) == dg.serialize(flat)
    # a third tier (edge hubs folding muxer rollups) composes too
    tiered = dg.merge(dg.merge(pre_a, dg.empty_digest()), pre_b)
    assert dg.serialize(tiered) == dg.serialize(flat)


def test_merge_into_matches_pure_merge():
    """The rollup's O(frame) in-place fold must compute exactly what
    the pure merge computes (snapshot normalizes the set-nodes form)."""
    regs = _sample_registries(4)
    ds = [dg.registry_digest(t, node=i + 1, seq=1, t=10.0 + i)
          for i, t in enumerate(regs)]
    acc = dg.empty_digest()
    for d in ds:
        dg.merge_into(acc, d)
    normalized = dg.merge(acc, dg.empty_digest())
    assert dg.serialize(normalized) == dg.serialize(dg.merge_all(ds))


def test_gauge_last_write_wins_total_order():
    a = dg.empty_digest()
    a["gauges"]["hub.nodes"] = [10.0, 5.0]
    b = dg.empty_digest()
    b["gauges"]["hub.nodes"] = [11.0, 3.0]
    assert dg.merge(a, b)["gauges"]["hub.nodes"] == [11.0, 3.0]
    assert dg.merge(b, a)["gauges"]["hub.nodes"] == [11.0, 3.0]
    # tie on t resolves by value — still order-insensitive
    b["gauges"]["hub.nodes"] = [10.0, 7.0]
    assert dg.merge(a, b)["gauges"]["hub.nodes"] \
        == dg.merge(b, a)["gauges"]["hub.nodes"] == [10.0, 7.0]


def test_digest_source_delta_reconstructs_registry():
    t = Telemetry()
    src = dg.DigestSource(7, telemetry=t)
    t.inc("comm.sent_msgs", 3, msg_type="X")
    t.observe("span.round_s", 1 / 4)
    d1 = src.next(t=1.0)
    t.inc("comm.sent_msgs", 2, msg_type="X")
    t.observe("span.round_s", 1 / 4)
    t.observe("span.round_s", 8.0)
    t.gauge_set("hub.nodes", 3)
    d2 = src.next(t=2.0)
    merged = dg.merge(d1, d2)
    full = dg.registry_digest(t, node=7, seq=2, t=2.0)
    assert dg.serialize(merged) == dg.serialize(full)
    # seq advanced per emission; an empty interval still heartbeats
    d3 = src.next(t=3.0)
    assert d3["sources"]["7"]["seq"] == 3
    assert not d3["counters"] and not d3["hists"]


def test_serialization_roundtrip_and_validate():
    d = dg.registry_digest(_sample_registries(2)[1], node=2, seq=4, t=9.0)
    blob = dg.serialize(d)
    back = dg.deserialize(blob)
    assert dg.serialize(back) == blob
    dg.validate(back)  # must not raise
    with pytest.raises(ValueError):
        dg.validate({"v": 99})
    with pytest.raises(ValueError):
        dg.validate({"v": 1, "counters": {"x": float("nan")}})
    with pytest.raises(ValueError):
        dg.validate([1, 2, 3])


def test_rollup_never_wedges_counts_everything():
    tel = Telemetry()
    r = dg.DigestRollup(telemetry=tel)
    t = Telemetry()
    src = dg.DigestSource(3, telemetry=t)
    t.inc("comm.sent_msgs", 1, msg_type="X")
    d1 = src.next(t=1.0)
    assert r.ingest(d1)
    # duplicate frame (same seq): skipped, counters not double-added
    assert not r.ingest(d1)
    assert r.snapshot()["counters"]["comm.sent_msgs{msg_type=X}"] == 1
    # garbage in every shape: rejected, never raises
    for bad in ({"v": 9}, "not json{", b"\xff\xfe", {"v": 1,
                "counters": {"k": float("inf")}}, None, 42):
        assert not r.ingest(bad)
    stats = r.stats()
    assert stats == {"frames": 1, "rejected": 6, "duplicates": 1,
                     "streams": 1}
    counters = tel.snapshot()["counters"]
    assert counters["digest.frames"] == 1
    assert counters["digest.dup_frames"] == 1
    assert sum(v for k, v in counters.items()
               if k.startswith("digest.rejected")) == 6


def test_rollup_tracks_lost_frames_and_staleness():
    r = dg.DigestRollup(telemetry=Telemetry())
    t = Telemetry()
    src = dg.DigestSource(5, nodes=[5, 6, 7], telemetry=t)
    r.ingest(src.next(t=1.0), t=1.0)
    src.next(t=2.0)  # emitted but "lost on the wire"
    src.next(t=3.0)  # lost too
    r.ingest(src.next(t=4.0), t=4.0)
    info = r.sources(now=4.5, stale_after=10.0)["5"]
    assert info["seq"] == 4 and info["lost_frames"] == 2
    assert info["nodes"] == 3 and not info["stale"]
    assert r.sources(now=30.0, stale_after=10.0)["5"]["stale"]
    assert r.covered_nodes() == [5, 6, 7]


# --- SLO engine --------------------------------------------------------------


def test_hist_quantile_bucket_upper_bound():
    h = {"count": 10, "sum": 5.0, "min": 0.3, "max": 6.0,
         "buckets": {"0.5": 5, "1.0": 4, "8.0": 1}}
    assert hist_quantile(h, 0.5) == 0.5
    assert hist_quantile(h, 0.9) == 1.0
    assert hist_quantile(h, 0.99) == 8.0
    assert hist_quantile({"count": 0, "buckets": {}}, 0.5) is None
    assert hist_quantile(None, 0.5) is None


def test_slo_spec_from_arg_inline_file_and_unknown(tmp_path):
    spec = SloSpec.from_arg('{"p99_round_wall_s": 5.0}')
    assert spec.p99_round_wall_s == 5.0 and spec.p50_round_wall_s is None
    path = tmp_path / "slo.json"
    path.write_text(json.dumps({"min_participation": 0.5,
                                "stale_after_s": 3.0}))
    spec = SloSpec.from_arg(str(path))
    assert spec.min_participation == 0.5 and spec.stale_after_s == 3.0
    with pytest.raises(ValueError):
        SloSpec.from_arg('{"not_a_field": 1}')
    # non-numeric thresholds must die at parse time, not as a swallowed
    # TypeError at every round close (the gate would read as passing)
    with pytest.raises(ValueError):
        SloSpec.from_arg('{"p99_round_wall_s": "5"}')
    with pytest.raises(ValueError):
        SloSpec.from_arg('{"min_participation": true}')
    with pytest.raises(ValueError):
        SloSpec.from_arg('{"stale_after_s": 0}')
    # null stale_after_s = derive from the report interval; the engine
    # resolves it to a concrete positive number either way
    eng = SloEngine(SloSpec.from_arg('{"stale_after_s": null}'),
                    telemetry=Telemetry())
    assert eng.spec.stale_after_s and eng.spec.stale_after_s > 0


def test_stale_streams_counts_every_missing_node():
    """max_stale_streams >= 1 must still fire when MANY nodes are dark
    (missing coverage counts per node, never a boolean +1)."""
    tel = Telemetry()
    eng = SloEngine(SloSpec(max_stale_streams=1, stale_after_s=0.001),
                    telemetry=tel)
    time.sleep(0.005)
    rollup = dg.registry_digest(tel, t=1.0)
    new = eng.evaluate(0, rollup, {}, expected_nodes=[1, 2, 3, 4])
    assert [v["objective"] for v in new] == ["stale_streams"]
    assert new[0]["observed"] == 4 and new[0]["threshold"] == 1


def test_stale_streams_grace_covers_startup():
    """A round closing before one staleness threshold of uptime must
    NOT flag every not-yet-reporting node as a violation (startup is
    not an outage)."""
    tel = Telemetry()
    eng = SloEngine(SloSpec(max_stale_streams=0), telemetry=tel)
    new = eng.evaluate(0, dg.registry_digest(tel, t=1.0), {},
                       expected_nodes=[1, 2, 3, 4])
    assert new == []


def test_validate_rejects_poisoned_bucket_bounds():
    """'nan'/'inf' bucket BOUNDS merge fine and then poison every
    downstream quantile ('nan > threshold' is False) — they must die
    at validate like any other non-finite input."""
    for bad_le in ("nan", "inf", "-1.0"):
        with pytest.raises(ValueError):
            dg.validate({"v": 1, "hists": {"h": {
                "count": 1, "sum": 1.0, "min": 1.0, "max": 1.0,
                "buckets": {bad_le: 1}}}})
    r = dg.DigestRollup(telemetry=Telemetry())
    assert not r.ingest({"v": 1, "hists": {"slo.round_wall_s": {
        "count": 2, "sum": 1.0, "min": 0.5, "max": 0.5,
        "buckets": {"nan": 2}}}})


def test_slo_engine_violations_counters_and_report():
    tel = Telemetry()
    eng = SloEngine(SloSpec(p50_round_wall_s=0.1, min_participation=0.9,
                            max_stale_streams=0, stale_after_s=0.001),
                    telemetry=tel)
    eng.observe_round(0, wall_s=0.4, round_bytes=2048.0, participants=4,
                      target=5)
    time.sleep(0.005)  # past the coverage grace window
    rollup = dg.registry_digest(tel, t=1.0)
    new = eng.evaluate(0, rollup, {"3": {"stale": True}},
                       expected_nodes=[1, 2, 3])
    objectives = {v["objective"] for v in new}
    assert objectives == {"round_wall_p50", "participation",
                          "stale_streams"}
    counters = tel.snapshot()["counters"]
    assert counters["slo.evaluations"] == 1
    assert counters["slo.violations{objective=round_wall_p50}"] == 1
    # violation events are in the ring for the metrics stream
    kinds = [e["kind"] for e in tel.drain_events()]
    assert kinds.count("slo_violation") == 3
    rep = eng.report(rollup, {"3": {"stale": True}},
                     expected_nodes=[1, 2, 3])
    assert rep["ok"] is False and rep["violations_total"] == 3
    assert rep["observed"]["round_wall_s"]["p50"] == 0.5  # bucket bound
    assert rep["observed"]["participation"]["last"] == pytest.approx(0.8)
    assert rep["stats_plane"]["stale_streams"] == ["3"]
    # expected nodes 1/2 never covered by any stream -> named missing
    assert rep["stats_plane"]["missing_nodes"] == [1, 2]


def test_empty_spec_reports_without_gating():
    tel = Telemetry()
    eng = SloEngine(SloSpec(), telemetry=tel)
    eng.observe_round(0, wall_s=1.0, round_bytes=100.0, participants=2,
                      target=2)
    assert eng.evaluate(0, dg.registry_digest(tel, t=1.0), {}) == []
    rep = eng.report(dg.registry_digest(tel, t=1.0), {})
    assert rep["ok"] is True
    assert rep["observed"]["round_wall_s"]["count"] == 1


def test_status_json_atomic_write_and_build(tmp_path):
    tel = Telemetry()
    eng = SloEngine(SloSpec(), telemetry=tel)
    rollup = dg.DigestRollup(telemetry=tel)
    src = dg.DigestSource(1, telemetry=tel)
    tel.inc("comm.sent_msgs", 2, msg_type="X")
    rollup.ingest(src.next(t=1.0), t=1.0)
    eng.observe_round(0, wall_s=0.5, round_bytes=10.0, participants=1,
                      target=1)
    rollup.ingest(src.next(t=2.0), t=2.0)
    status = build_status(eng, rollup, round_idx=1, rounds_total=3,
                          expected_nodes=[1], now=2.5)
    path = str(tmp_path / "status.json")
    write_json_atomic(path, status)
    back = json.load(open(path))
    assert back["round"] == 1 and back["slo"]["ok"] is True
    assert back["stats_plane"]["streams"] == 1
    assert back["sources"]["1"]["seq"] == 2
    assert back["round_wall_s"]["count"] == 1
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("status.json.tmp")], "tmp file left behind"


# --- wire + server integration ----------------------------------------------


def test_digest_frame_roundtrips_the_tcp_hub():
    """A DigestReporter's frame crosses a real hub and reconstitutes
    losslessly (the digest dict is plain JSON in the frame header — no
    binary payload) into a receiving rollup."""
    from fedml_tpu.comm.message import MSG_TYPE_C2S_TELEMETRY
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    hub = TcpHub()
    got = []
    server = client = None
    try:
        server = TcpBackend(0, hub.host, hub.port)

        class _Sink:
            def receive_message(self, t, m):
                got.append((t, m))

        server.add_observer(_Sink())
        server.run_in_thread()
        client = TcpBackend(4, hub.host, hub.port)
        client.await_peers([0])
        tel = Telemetry()
        tel.inc("comm.sent_bytes", 4096, msg_type="C2S_SEND_MODEL")
        tel.observe("span.round_s", 0.5)
        rep = dg.DigestReporter(client, interval=30.0, nodes=[4],
                                telemetry=tel)
        rep._tick()  # one frame, no thread needed
        deadline = time.monotonic() + 10
        while not got and time.monotonic() < deadline:
            time.sleep(0.02)
        assert got, "digest frame never arrived"
        msg_type, msg = got[0]
        assert msg_type == MSG_TYPE_C2S_TELEMETRY
        rollup = dg.DigestRollup(telemetry=Telemetry())
        assert rollup.ingest(msg.get(dg.DIGEST_KEY))
        snap = rollup.snapshot()
        assert snap["counters"][
            "comm.sent_bytes{msg_type=C2S_SEND_MODEL}"] == 4096
        assert snap["hists"]["span.round_s"]["count"] == 1
        assert rollup.sources(now=time.time())["4"]["seq"] == 1
    finally:
        for b in (client, server):
            if b is not None:
                b.stop()
        hub.stop()


def test_server_manager_ingests_and_survives_garbage():
    """The server's telemetry handler must merge good digests and shrug
    off corrupted ones — without a backend or a round in flight."""
    from fedml_tpu.comm.message import (
        MSG_TYPE_C2S_TELEMETRY,
        Message,
    )

    class _NullBackend:
        node_id = 0

        def add_observer(self, obs):
            pass

    import numpy as np

    from fedml_tpu.algorithms.fedavg_cross_device import FedAvgServerManager

    mgr = FedAvgServerManager(
        _NullBackend(), {"w": np.zeros(2, np.float32)}, num_clients=2,
        clients_per_round=2, comm_rounds=1, stats_plane=True,
    )
    tel = Telemetry()
    tel.inc("comm.sent_msgs", 1, msg_type="X")
    good = Message(MSG_TYPE_C2S_TELEMETRY, 1, 0)
    good.add_params(dg.DIGEST_KEY,
                    dg.registry_digest(tel, node=1, seq=1, t=1.0))
    mgr._on_telemetry(good)
    bad = Message(MSG_TYPE_C2S_TELEMETRY, 2, 0)
    bad.add_params(dg.DIGEST_KEY, {"v": 1,
                                   "counters": {"x": float("nan")}})
    mgr._on_telemetry(bad)  # must not raise
    missing = Message(MSG_TYPE_C2S_TELEMETRY, 2, 0)
    mgr._on_telemetry(missing)  # no digest key at all
    stats = mgr.rollup.stats()
    assert stats["frames"] == 1 and stats["rejected"] == 2
    summary = mgr.stats_summary()
    assert summary["enabled"] and summary["streams_remote"] == 1


def test_multiprocess_federation_stats_plane(tmp_path):
    """Acceptance shape on the real process topology: 4 clients where
    2 ride ONE muxer = 3 client-side connections; digest streams must
    equal CONNECTIONS (not clients), status.json + slo_report.json land
    in run_dir, and the in-band round-wall p50 sits within one log2
    bucket of the post-hoc exact number."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / "final.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    info = {}
    rc = launch(
        num_clients=4, rounds=2, seed=0, batch_size=16, out_path=out,
        run_dir=str(tmp_path), round_timeout=60.0,
        muxers=1, muxed_clients=2,
        slo=json.dumps({"p99_round_wall_s": 120.0,
                        "max_corrupt_uploads": 0}),
        env=env, info=info, timeout=240.0,
    )
    assert rc == 0, "federation failed"
    sp = info.get("stats_plane") or {}
    assert sp.get("enabled") is True
    # 1 muxer conn (2 virtual clients) + 2 plain clients = 3 streams
    assert sp.get("streams_remote") == 3, sp
    assert sp.get("missing_nodes_total") == 0
    assert sp.get("slo_ok") is True, sp
    report = json.load(open(tmp_path / "slo_report.json"))
    assert report["ok"] is True
    assert report["rounds_evaluated"] == 2
    assert report["stats_plane"]["streams"] == 4  # 3 remote + server local
    wall = report["observed"]["round_wall_s"]
    assert wall["count"] == 2 and wall["p50"] is not None
    status = json.load(open(tmp_path / "status.json"))
    assert status["finished"] is True and status["round"] == 2
    # in-band p50 (bucket upper bound) within one log2 bucket of the
    # exact post-hoc number from the same run's metrics files
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.fed_timeline import build_rounds, load_run, percentile

    rows = build_rounds(load_run(str(tmp_path)))
    exact = percentile([r.get("wall_s") for r in rows], 0.5)
    assert exact is not None and exact > 0
    assert abs(math.ceil(math.log2(wall["p50"]))
               - math.ceil(math.log2(exact))) <= 1


# --- tools -------------------------------------------------------------------


def test_fed_slo_tool_renders_and_json(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools import fed_slo

    tel = Telemetry()
    eng = SloEngine(SloSpec(), telemetry=tel)
    rollup = dg.DigestRollup(telemetry=tel)
    src = dg.DigestSource(2, telemetry=tel)
    tel.observe("slo.round_wall_s", 0.25)
    rollup.ingest(src.next(t=1.0), t=1.0)
    write_json_atomic(str(tmp_path / "status.json"), build_status(
        eng, rollup, round_idx=1, rounds_total=2, now=1.5))
    assert fed_slo.main([str(tmp_path)]) == 0
    human = capsys.readouterr().out
    assert "RUNNING" in human and "round 1/2" in human
    assert fed_slo.main([str(tmp_path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["status"]["round"] == 1 and doc["report"] is None
    assert fed_slo.main([str(tmp_path / "nothing_here")]) == 2


def test_bench_trend_over_repo_artifacts(capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools import bench_trend

    root = os.path.join(os.path.dirname(__file__), "..")
    records = bench_trend.collect(root)
    assert len(records) >= 30, "the checked-in artifact set should parse"
    by_name = {r["artifact"]: r for r in records}
    assert by_name["FEDSCALE_r10.json"]["round"] == 10
    assert by_name["FEDSCALE_r10.json"]["metrics"]["clients"] == 10000
    assert by_name["FAULTS_r10.json"]["metrics"]["survived"] == 8
    assert by_name["COMPRESS_FEDERATION_r06.json"]["metrics"][
        "reduction_ratio"] == 4.91
    # no artifact may crash the collector — errors are per-record
    assert all("metrics" in r for r in records)
    assert bench_trend.main(["--dir", root, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["artifacts"] == len(records)


def test_reporter_thread_emits_and_stops():
    """The reporter loop emits on its interval and stop() is idempotent
    with a final flush."""
    sent = []

    class _FakeBackend:
        node_id = 9

        def send_message(self, msg):
            sent.append(msg)

    tel = Telemetry()
    rep = dg.DigestReporter(_FakeBackend(), interval=0.05, nodes=[9],
                            telemetry=tel)
    tel.inc("comm.sent_msgs", 1, msg_type="X")
    rep.start()
    deadline = time.monotonic() + 10
    while len(sent) < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    rep.stop()
    rep.stop()  # idempotent
    n = len(sent)
    assert n >= 2
    time.sleep(0.15)
    assert len(sent) == n, "reporter kept sending after stop()"
    # frames carry strictly increasing seqs for one source
    seqs = [m.get(dg.DIGEST_KEY)["sources"]["9"]["seq"] for m in sent]
    assert seqs == sorted(set(seqs))
    assert tel.snapshot()["counters"]["digest.sent"] == len(sent)


def test_reporter_backlog_carries_failed_interval():
    """A failed send's delta must ride the next successful frame — no
    interval's counters may silently vanish from the rollup."""
    sent = []
    fail = {"on": True}

    class _FlakyBackend:
        node_id = 9

        def send_message(self, msg):
            if fail["on"]:
                raise OSError("hub mid-restart")
            sent.append(msg)

    tel = Telemetry()
    rep = dg.DigestReporter(_FlakyBackend(), interval=30.0, nodes=[9],
                            telemetry=tel)
    tel.inc("comm.sent_msgs", 3, msg_type="X")
    rep._tick()  # consumed but lost on the wire
    assert not sent
    tel.inc("comm.sent_msgs", 2, msg_type="X")
    fail["on"] = False
    rep._tick()  # catch-up frame
    assert len(sent) == 1
    d = sent[0].get(dg.DIGEST_KEY)
    assert d["counters"]["comm.sent_msgs{msg_type=X}"] == 5
    rollup = dg.DigestRollup(telemetry=Telemetry())
    assert rollup.ingest(d)
    # the failed frame's seq is honestly a gap (it never arrived)
    assert rollup.sources(now=time.time())["9"]["lost_frames"] == 1
