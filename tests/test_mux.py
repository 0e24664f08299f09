"""Virtual-client multiplexing (PR 10): hello v2, per-connection
broadcast dedup, local demux, the vmapped cohort engine, and the
pinned muxed-vs-per-process byte-identity contract.

In-process tests drive a real ``TcpHub`` + ``TcpMuxBackend`` over
loopback sockets; the federation tests spawn the true multi-process
topology (``experiments/distributed_fedavg.launch``) with one or more
``--role muxer`` processes and compare upload digests against the
one-process-per-client path — same seed, same bytes, both fp32 and
int8+EF (the fold_in streams are pure functions of (seed, round,
slot), so this is testable byte-for-byte).
"""

import json
import os
import time

import numpy as np
import pytest

from fedml_tpu.comm.message import Message
from fedml_tpu.comm.mux import TcpMuxBackend
from fedml_tpu.comm.tcp import TcpBackend, TcpHub
from fedml_tpu.obs import trace_ctx
from fedml_tpu.obs.telemetry import get_telemetry


class _Collect:
    def __init__(self, sink, key):
        self.sink, self.key = sink, key

    def receive_message(self, t, m):
        self.sink.setdefault(self.key, []).append(m)


def _wait(cond, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert cond(), "condition never held"


def _counters():
    return get_telemetry().snapshot()["counters"]


def test_hello_v2_mcast_one_frame_per_connection():
    """A broadcast to 3 co-located virtual clients + 1 plain client
    crosses the wire as ONE wrapped frame per connection (per-conn
    dedup), and the demux delivers a per-virtual clone — correct
    receiver, shared payload bytes, per-virtual trace hop stamps."""
    trace_ctx.set_enabled(True)
    hub = TcpHub()
    got = {}
    mux = plain = sender = None
    try:
        mux = TcpMuxBackend([1, 2, 3], hub.host, hub.port)
        for i in (1, 2, 3):
            mux.virtual(i).add_observer(_Collect(got, i))
        mux.run_in_thread()
        plain = TcpBackend(4, hub.host, hub.port)
        plain.add_observer(_Collect(got, 4))
        plain.run_in_thread()
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([1, 2, 3, 4])
        payload = np.arange(50_000, dtype=np.float32)
        m = Message("MUXCAST", 9, -1)
        m.add_params("model", payload)
        before = _counters()
        sender.send_multicast(m, [1, 2, 3, 4])
        _wait(lambda: all(got.get(i) for i in (1, 2, 3, 4)))
        hops_per_node = {}
        for i in (1, 2, 3, 4):
            back = got[i][0]
            # demux rewrites each virtual clone's receiver; the plain
            # node keeps the shared envelope's -1 (the pre-mux
            # multicast contract: identity derives from the node id)
            assert back.receiver == (i if i != 4 else -1)
            np.testing.assert_array_equal(
                np.asarray(back.get("model")), payload)
            ctx = back.params.get(trace_ctx.TRACE_KEY)
            assert ctx is not None
            hops_per_node[i] = ctx["hops"]
            # per-virtual recv stamp on a SHARED physical frame
            assert [h for h in ctx["hops"] if h[1] == "recv"][0][0] == i
        # per-clone hop lists never alias (copy-on-write stamping)
        assert len({id(h) for h in hops_per_node.values()}) == 4
        after = _counters()
        # ONE wrapped frame for the whole virtual trio
        assert after.get("comm.mux_frames{msg_type=MUXCAST}", 0) \
            - before.get("comm.mux_frames{msg_type=MUXCAST}", 0) == 1
        assert after.get("comm.mux_deliveries{msg_type=MUXCAST}", 0) \
            - before.get("comm.mux_deliveries{msg_type=MUXCAST}", 0) == 3
        stats = hub.stats()
        assert stats["nodes"] >= 5 and stats["connections"] < stats["nodes"]
    finally:
        for b in (mux, plain, sender):
            if b is not None:
                b.stop()
        hub.stop()
        trace_ctx.set_enabled(None)


def test_striped_mcast_to_muxed_conn_reassembles_and_fans_out():
    """Striped fan-out composes with muxing: the stripe stream crosses
    once per CONNECTION (stripe 0 carries the co-located ids) and the
    reassembled frame demuxes to every virtual node."""
    hub = TcpHub(stripe_bytes=1024)
    got = {}
    mux = sender = None
    try:
        mux = TcpMuxBackend([1, 2], hub.host, hub.port)
        for i in (1, 2):
            mux.virtual(i).add_observer(_Collect(got, i))
        mux.run_in_thread()
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([1, 2])
        payload = np.arange(5_000, dtype=np.float32)  # 20 KB -> stripes
        m = Message("STRIPED", 9, -1)
        m.add_params("model", payload)
        before = _counters()
        sender.send_multicast(m, [1, 2])
        _wait(lambda: all(got.get(i) for i in (1, 2)))
        for i in (1, 2):
            np.testing.assert_array_equal(
                np.asarray(got[i][0].get("model")), payload)
        after = _counters()
        # ONE reassembly (one physical stripe stream), two deliveries
        assert after.get("comm.stripe_reassemblies{msg_type=STRIPED}", 0) \
            - before.get("comm.stripe_reassemblies{msg_type=STRIPED}", 0) == 1
        assert hub.stats()["striped_mcasts"] == 1
    finally:
        for b in (mux, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_node_rebind_two_live_conns_new_conn_wins():
    """Duplicate-registration policy (pinned): a second live connection
    claiming a registered id wins it — frames route to the NEW conn,
    the displaced one is dropped and counted (hub.node_rebinds)."""
    hub = TcpHub()
    got = {}
    first = second = sender = None
    try:
        first = TcpBackend(7, hub.host, hub.port)
        first.add_observer(_Collect(got, "first"))
        t_first = first.run_in_thread()
        second = TcpBackend(7, hub.host, hub.port)
        second.add_observer(_Collect(got, "second"))
        second.run_in_thread()
        _wait(lambda: hub.stats()["node_rebinds"] == 1)
        # the displaced conn is CLOSED by the hub: its reader exits
        t_first.join(timeout=10)
        assert not t_first.is_alive()
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([7])
        m = Message("REBIND", 9, 7)
        m.add_params("x", 1)
        sender.send_message(m)
        _wait(lambda: got.get("second"))
        assert not got.get("first")
        assert hub.stats()["connections"] == 2  # second + sender
    finally:
        for b in (first, second, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_node_rebind_reconnect_case():
    """The reconnect shape of the same policy: the old conn is a
    silently-dead peer (wedged process, no FIN yet) — the re-dial must
    claim the id immediately instead of racing the old conn's cleanup,
    and routing must follow the new conn."""
    hub = TcpHub()
    got = {}
    stale = fresh = sender = None
    try:
        stale = TcpBackend(5, hub.host, hub.port)  # never runs a reader
        fresh = TcpBackend(5, hub.host, hub.port)
        fresh.add_observer(_Collect(got, "fresh"))
        fresh.run_in_thread()
        _wait(lambda: hub.stats()["node_rebinds"] == 1)
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([5])
        m = Message("RECON", 9, 5)
        m.add_params("x", 1)
        sender.send_message(m)
        _wait(lambda: got.get("fresh"))
    finally:
        for b in (stale, fresh, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_mux_partial_rebind_keeps_other_virtual_ids_alive():
    """Rebinding ONE of a muxer's ids must not kill its siblings: the
    conn only dies when it holds no ids at all."""
    hub = TcpHub()
    got = {}
    mux = claimer = sender = None
    try:
        mux = TcpMuxBackend([1, 2, 3], hub.host, hub.port)
        for i in (1, 2, 3):
            mux.virtual(i).add_observer(_Collect(got, i))
        mux.run_in_thread()
        claimer = TcpBackend(2, hub.host, hub.port)  # steals virtual id 2
        claimer.add_observer(_Collect(got, "claimer"))
        claimer.run_in_thread()
        _wait(lambda: hub.stats()["node_rebinds"] == 1)
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([1, 2, 3])
        for r in (1, 2, 3):
            m = Message("PARTIAL", 9, r)
            m.add_params("x", r)
            sender.send_message(m)
        _wait(lambda: got.get(1) and got.get(3) and got.get("claimer"))
        assert not got.get(2)  # the muxer no longer owns id 2
    finally:
        for b in (mux, claimer, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_per_virtual_node_chaos_decisions_on_shared_conn():
    """FaultRule parity: a recv drop rule scoped to virtual node 2
    drops ONLY node 2's copy of a broadcast that arrived as one shared
    physical frame — nodes 1 and 3 still deliver."""
    from fedml_tpu.faults import ChaosBackend, FaultPlan, FaultRule

    plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="drop", node=2, msg_type="CH",
                         direction="recv")],
        msg_types=("CH",),
    )
    hub = TcpHub()
    got = {}
    mux = sender = None
    try:
        mux = TcpMuxBackend([1, 2, 3], hub.host, hub.port)
        wrapped = {i: ChaosBackend(mux.virtual(i), plan) for i in (1, 2, 3)}
        for i, w in wrapped.items():
            w.add_observer(_Collect(got, i))
        mux.run_in_thread()
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([1, 2, 3])
        m = Message("CH", 9, -1)
        m.add_params("x", 1)
        before = _counters()
        sender.send_multicast(m, [1, 2, 3])
        _wait(lambda: got.get(1) and got.get(3))
        time.sleep(0.2)  # node 2's copy must NOT trickle in late
        assert not got.get(2)
        after = _counters()
        assert after.get("faults.injected{action=drop,msg_type=CH}", 0) \
            - before.get("faults.injected{action=drop,msg_type=CH}", 0) == 1
    finally:
        for b in (mux, sender):
            if b is not None:
                b.stop()
        hub.stop()


# --- multi-process federations ----------------------------------------------


def _fed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def _digests(info):
    return {k: v for k, v in sorted(info.items())
            if k.endswith("_upload_digest")}


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_muxed_uploads_byte_identical_to_per_process(tmp_path, codec):
    """THE acceptance pin: same seed, same codec — a muxed federation's
    per-virtual-client upload digests equal the one-process-per-client
    federation's, byte for byte (fp32 full models and int8+EF deltas),
    and the final global models are bit-equal."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    env = _fed_env()
    results = {}
    for tag, muxers in (("proc", 0), ("mux", 1)):
        out = str(tmp_path / f"final_{tag}_{codec}.npz")
        info = {}
        rc = launch(num_clients=3, rounds=2, seed=0, batch_size=16,
                    out_path=out, codec=codec, muxers=muxers,
                    env=env, info=info, timeout=240.0)
        assert rc == 0, f"{tag}/{codec} federation failed"
        z = np.load(out)
        leaves = [np.asarray(z[k]) for k in sorted(z.files)
                  if k.startswith("leaf_")]
        results[tag] = (_digests(info), leaves)
    dig_proc, leaves_proc = results["proc"]
    dig_mux, leaves_mux = results["mux"]
    assert len(dig_proc) == 3 and dig_proc == dig_mux
    for a, b in zip(leaves_proc, leaves_mux):
        np.testing.assert_array_equal(a, b)


def test_mixed_cohort_muxed_plus_v1_dialers(tmp_path):
    """A MIXED federation: clients 1-3 ride one muxer (hello v2), 4-5
    run as plain processes whose backends still dial with the original
    single-id hello — both shapes interop on one hub and every round
    aggregates the full cohort."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / "final_mixed.npz")
    info = {}
    rc = launch(num_clients=5, rounds=2, seed=0, batch_size=16,
                out_path=out, muxers=1, muxed_clients=3,
                env=_fed_env(), info=info, timeout=240.0)
    assert rc == 0
    z = np.load(out)
    assert int(z["rounds"]) == 2
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert all(r["participants"] == [1, 2, 3, 4, 5] for r in rounds)
    # one digest line per client regardless of topology
    assert len(_digests(info)) == 5


def test_mux_smoke_64_virtual_clients(tmp_path):
    """Tier-1 smoke: a 64-virtual-client federation on ONE muxer
    process (67 OS processes under the old shape, 4 here) completes its
    rounds with the full cohort aggregating — the cheap end of the
    FEDSCALE_r10 10k benchmark, run in CI."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / "final_64.npz")
    rc = launch(num_clients=64, rounds=2, seed=0, batch_size=16,
                out_path=out, muxers=1, env=_fed_env(), timeout=300.0)
    assert rc == 0
    z = np.load(out)
    assert int(z["rounds"]) == 2
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert all(r["participants"] == list(range(1, 65)) for r in rounds)
    for i in range(len([k for k in z.files if k.startswith("leaf_")])):
        assert np.isfinite(z[f"leaf_{i}"]).all()
