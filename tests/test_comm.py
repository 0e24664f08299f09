"""Comm layer tests: message codec, inproc bus, TCP hub, cross-device
FedAvg choreography — and its equivalence with the compiled simulation."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
from fedml_tpu.algorithms.fedavg_cross_device import (
    FedAvgClientManager,
    FedAvgServerManager,
)
from fedml_tpu.comm.inproc import InprocBus
from fedml_tpu.comm.message import (
    MSG_TYPE_C2S_SEND_MODEL,
    Message,
    list_to_tensor,
    tensor_to_list,
    tree_from_wire,
    tree_to_wire,
)
from fedml_tpu.comm.tcp import TcpBackend, TcpHub
from fedml_tpu.core.client import make_client_optimizer, make_local_update
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models.linear import logistic_regression


def test_message_json_roundtrip_with_arrays():
    m = Message(MSG_TYPE_C2S_SEND_MODEL, 3, 0)
    m.add_params("weights", np.arange(6, dtype=np.float32).reshape(2, 3))
    m.add_params("n", 42)
    back = Message.from_json(m.to_json())
    assert back.type == MSG_TYPE_C2S_SEND_MODEL
    assert back.sender == 3 and back.receiver == 0
    np.testing.assert_allclose(back.get("weights"), m.get("weights"))
    assert back.get("n") == 42


def test_pytree_wire_roundtrip():
    tree = {"params": {"w": jnp.ones((3, 2)), "b": jnp.arange(2.0)}}
    wire = tree_to_wire(tree)
    back = tree_from_wire(wire, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_tensor_list_codec():
    tree = {"w": np.ones((2, 2), np.float32)}
    lists = tensor_to_list(tree)
    assert lists["w"] == [[1.0, 1.0], [1.0, 1.0]]
    back = list_to_tensor(lists)
    np.testing.assert_allclose(back["w"], tree["w"])


def _build_federation(bus_or_backends, ds, cfg):
    bundle = logistic_regression(16, 4)
    init = bundle.init(jax.random.PRNGKey(cfg.seed))
    opt = make_client_optimizer("sgd", cfg.lr, momentum=cfg.momentum)
    lu = make_local_update(bundle, opt, cfg.epochs)
    return bundle, init, lu


def test_cross_device_fedavg_inproc_matches_simulation():
    """The message-driven federation must produce numerically identical
    global weights to the compiled simulation when both use full
    participation and the same local operator — including under a
    HETEROGENEOUS partition with a stateful (momentum) optimizer, where
    pack geometry differences would change trajectories."""
    import numpy as _np

    ds = synthetic_classification(
        num_train=240, num_test=60, input_shape=(16,), num_classes=4,
        num_clients=3, partition="hetero", partition_alpha=0.4, seed=0,
    )
    assert len(set(ds.client_sample_counts().tolist())) > 1  # truly hetero
    cfg = FedAvgConfig(
        num_clients=3, clients_per_round=3, comm_rounds=3, epochs=1,
        batch_size=16, lr=0.1, momentum=0.9, frequency_of_the_test=100, seed=0,
    )
    bundle, init, lu = _build_federation(None, ds, cfg)
    steps = int(_np.ceil(ds.client_sample_counts().max() / 16))

    bus = InprocBus()
    server = FedAvgServerManager(
        bus.register(0), init,
        num_clients=3, clients_per_round=3, comm_rounds=3, seed=0,
        steps_per_epoch=steps,
    )
    clients = [
        FedAvgClientManager(
            bus.register(i + 1), lu, ds, batch_size=16,
            template_variables=init, seed=0,
        )
        for i in range(3)
    ]
    server.start()
    bus.drain()
    assert server.round_idx == 3
    assert len(server.round_log) == 3
    assert all(c.rounds_trained >= 1 for c in clients)

    sim = FedAvgSimulation(bundle, ds, cfg)
    sim.run()
    # same init, same sampling (full), same rng scheme per (round, client)
    for a, b in zip(
        jax.tree_util.tree_leaves(server.variables),
        jax.tree_util.tree_leaves(sim.state.variables),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_cross_device_fedavg_tcp():
    """Same choreography across real sockets (the DCN/mobile path)."""
    ds = synthetic_classification(
        num_train=120, num_test=30, input_shape=(8,), num_classes=2,
        num_clients=2, partition="homo", seed=1,
    )
    bundle = logistic_regression(8, 2)
    init = bundle.init(jax.random.PRNGKey(1))
    opt = make_client_optimizer("sgd", 0.1)
    lu = make_local_update(bundle, opt, 1)

    hub = TcpHub()
    server_backend = TcpBackend(0, hub.host, hub.port)
    client_backends = [TcpBackend(i + 1, hub.host, hub.port) for i in range(2)]
    server = FedAvgServerManager(
        server_backend, init, num_clients=2, clients_per_round=2,
        comm_rounds=2, seed=1,
    )
    clients = [
        FedAvgClientManager(
            cb, lu, ds, batch_size=16, template_variables=init, seed=1
        )
        for cb in client_backends
    ]
    threads = [cb.run_in_thread() for cb in client_backends]
    server_thread = server_backend.run_in_thread()
    server.start()
    server_thread.join(timeout=60)
    assert not server_thread.is_alive(), "server did not finish in time"
    assert server.round_idx == 2
    for t in threads:
        t.join(timeout=10)
    hub.stop()
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(server.variables))


def test_message_frame_parts_cached_and_join_equals_frame():
    """to_frame_parts is the zero-copy encoding: its concatenation IS
    to_frame(), it is memoized on the message (broadcast fan-out and
    retries reuse ONE immutable buffer list), and add_params
    invalidates the memo."""
    m = Message(MSG_TYPE_C2S_SEND_MODEL, 1, 0)
    m.add_params("weights", np.arange(12, dtype=np.float32).reshape(3, 4))
    parts = m.to_frame_parts()
    assert b"".join(parts) == m.to_frame()
    assert m.to_frame_parts() is parts  # memoized
    m.add_params("extra", 1)
    parts2 = m.to_frame_parts()
    assert parts2 is not parts  # invalidated by the param change
    # a message without arrays is a single v1-identical JSON line
    plain = Message("X", 1, 0)
    assert plain.to_frame_parts() == [(plain.to_json() + "\n").encode()]


def test_hub_multicast_fans_out_one_payload():
    """One ``__hub__: mcast`` frame reaches every receiver byte-
    identical, while the sender's wire accounting shows the payload was
    shipped to the hub exactly ONCE (the O(model)-per-round broadcast
    contract)."""
    import time

    from fedml_tpu.obs.telemetry import get_telemetry

    hub = TcpHub()
    got = {1: [], 2: [], 3: []}

    class Obs:
        def __init__(self, i):
            self.i = i

        def receive_message(self, t, m):
            got[self.i].append(m)

    receivers = []
    for i in (1, 2, 3):
        b = TcpBackend(i, hub.host, hub.port)
        b.add_observer(Obs(i))
        b.run_in_thread()
        receivers.append(b)
    sender = TcpBackend(9, hub.host, hub.port)
    sender.await_peers([1, 2, 3])
    payload = np.arange(300_000, dtype=np.float32)  # 1.2 MB
    m = Message("MCAST_PIN", 9, -1)
    m.add_params("model", payload)
    before = get_telemetry().snapshot()["counters"]
    sender.send_multicast(m, [1, 2, 3])
    deadline = time.monotonic() + 15
    while any(not got[i] for i in (1, 2, 3)) and time.monotonic() < deadline:
        time.sleep(0.02)
    for i in (1, 2, 3):
        assert got[i], f"node {i} never received the multicast"
        back = got[i][0]
        assert back.sender == 9
        np.testing.assert_array_equal(np.asarray(back.get("model")), payload)
    after = get_telemetry().snapshot()["counters"]
    key = "comm.sent_bytes{msg_type=MCAST_PIN}"
    sent = after.get(key, 0) - before.get(key, 0)
    # one payload + headers — NOT three copies
    assert payload.nbytes <= sent < 2 * payload.nbytes
    stats = hub.stats()
    assert stats["mcast_frames"] == 1 and stats["mcast_copies"] == 3
    for b in receivers:
        b.stop()
    sender.stop()
    hub.stop()


def test_deep_pytree_frame_exceeding_iov_max_roundtrips():
    """A frame with more buffers than IOV_MAX (one per array leaf) must
    still send — _sendall_parts chunks the vectored write instead of
    letting sendmsg fail with EMSGSIZE."""
    import time

    from fedml_tpu.comm.tcp import _IOV_MAX

    n_leaves = _IOV_MAX + 200
    leaves = [np.full((3,), float(i), np.float32) for i in range(n_leaves)]
    hub = TcpHub()
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append(m)

    receiver = TcpBackend(1, hub.host, hub.port)
    receiver.add_observer(Obs())
    receiver.run_in_thread()
    sender = TcpBackend(2, hub.host, hub.port)
    sender.await_peers([1])
    m = Message("DEEP", 2, 1)
    m.add_params("leaves", leaves)
    assert len(m.to_frame_parts()) > _IOV_MAX
    sender.send_message(m)
    deadline = time.monotonic() + 15
    while not got and time.monotonic() < deadline:
        time.sleep(0.02)
    assert got, "deep-pytree frame never arrived"
    back = got[0].get("leaves")
    assert len(back) == n_leaves
    for i in (0, n_leaves // 2, n_leaves - 1):
        np.testing.assert_array_equal(np.asarray(back[i]), leaves[i])
    receiver.stop()
    sender.stop()
    hub.stop()


def test_multicast_base_fallback_unicast_clones():
    """Transports without a native fan-out (inproc) deliver per-receiver
    clones of ONE message: correct receiver ids, shared payload."""
    bus = InprocBus()
    sender = bus.register(0)
    got = {}

    class Obs:
        def __init__(self, i):
            self.i = i

        def receive_message(self, t, m):
            got[self.i] = m

    for i in (1, 2):
        b = bus.register(i)
        b.add_observer(Obs(i))
    m = Message("X", 0, -1)
    w = np.ones((2, 2), np.float32)
    m.add_params("w", w)
    sender.send_multicast(m, [1, 2])
    bus.drain()
    assert got[1].receiver == 1 and got[2].receiver == 2
    assert got[1].get("w") is w and got[2].get("w") is w  # shared, not copied


def test_tcp_socket_options_applied():
    """TCP_NODELAY + sized SO_SNDBUF/SO_RCVBUF on both ends of a hub
    connection (multi-MB frames must not ride Nagle + default buffers)."""
    import socket as _socket
    import time

    hub = TcpHub()
    b = TcpBackend(1, hub.host, hub.port)
    deadline = time.monotonic() + 5
    while 1 not in hub._conns and time.monotonic() < deadline:
        time.sleep(0.01)
    for sock in (b._sock, hub._conns[1].sock):
        assert sock.getsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY) != 0
        # the kernel clamps SO_*BUF to net.core.*mem_max — assert a
        # floor well above the pre-tuning default rather than the exact
        # requested size
        assert sock.getsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF) >= 64 * 1024
        assert sock.getsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF) >= 64 * 1024
    b.stop()
    hub.stop()


def test_concurrent_send_frame_integrity_under_multicast():
    """Stress: several threads pushing multi-MB v2 frames through ONE
    TcpBackend while another backend multicasts to the same receiver —
    every frame must arrive whole and byte-identical (pins the
    per-connection queue + single-drainer design; tearing would show up
    as mixed-tag payloads or undecodable frames)."""
    import threading as _threading
    import time

    hub = TcpHub()
    recv = []
    recv_lock = _threading.Lock()

    class Obs:
        def receive_message(self, t, m):
            with recv_lock:
                recv.append((m.get("tag"), np.asarray(m.get("data"))))

    receiver = TcpBackend(1, hub.host, hub.port)
    receiver.add_observer(Obs())
    receiver.run_in_thread()
    sender = TcpBackend(2, hub.host, hub.port)
    mcaster = TcpBackend(3, hub.host, hub.port)
    sender.await_peers([1])
    mcaster.await_peers([1])

    nthreads, nframes, size = 4, 3, 400_000  # 1.6 MB per frame

    def blast(tid):
        for k in range(nframes):
            tag = tid * 100 + k
            m = Message("STRESS", 2, 1)
            m.add_params("tag", tag)
            m.add_params("data", np.full(size, float(tag), np.float32))
            sender.send_message(m)

    threads = [_threading.Thread(target=blast, args=(i,)) for i in range(nthreads)]
    for t in threads:
        t.start()
    for j in range(nframes):
        tag = 1000 + j
        mm = Message("STRESS", 3, -1)
        mm.add_params("tag", tag)
        mm.add_params("data", np.full(size, float(tag), np.float32))
        mcaster.send_multicast(mm, [1])
    for t in threads:
        t.join(timeout=30)
    want = nthreads * nframes + nframes
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with recv_lock:
            if len(recv) >= want:
                break
        time.sleep(0.05)
    with recv_lock:
        frames = list(recv)
    assert len(frames) == want, f"lost frames: {len(frames)}/{want}"
    for tag, arr in frames:
        assert arr.shape == (size,)
        np.testing.assert_array_equal(arr, np.full(size, float(tag), np.float32))
    for b in (receiver, sender, mcaster):
        b.stop()
    hub.stop()


def test_streaming_aggregation_leaf_exact_mixed_cohort():
    """The streaming fold (sum n·x on arrival, normalize at close) is
    BIT-identical to the buffered reference ``tree_weighted_mean`` over
    the accepted cohort — with a corrupt upload rejected, a stale
    upload discarded, and over-sampled spares left out — and agrees
    with the legacy ``tree_weighted_sum`` math to float tolerance."""
    from fedml_tpu.comm.message import (MSG_ARG_KEY_MODEL_PARAMS,
                                        MSG_ARG_KEY_NUM_SAMPLES,
                                        MSG_ARG_KEY_ROUND_INDEX)
    from fedml_tpu.core import tree as treelib

    bus = InprocBus()
    server_backend = bus.register(0)
    for i in range(1, 6):
        bus.register(i)
    init = {"params": {"w": np.ones((4, 3), np.float32),
                       "b": np.zeros((3,), np.float32)}}
    server = FedAvgServerManager(
        server_backend, init, num_clients=5, clients_per_round=3,
        comm_rounds=2, seed=0, spares=2,
    )
    assert server.streaming_agg  # the default hot path

    def upload(sender, tree, n, round_idx=0):
        m = Message(MSG_TYPE_C2S_SEND_MODEL, sender, 0)
        m.add_params(MSG_ARG_KEY_ROUND_INDEX, round_idx)
        m.add_params(MSG_ARG_KEY_MODEL_PARAMS, tree_to_wire(tree))
        m.add_params(MSG_ARG_KEY_NUM_SAMPLES, float(n))
        server._on_model(m)

    rng = np.random.RandomState(7)

    def rand_tree():
        return {"params": {"w": rng.randn(4, 3).astype(np.float32),
                           "b": rng.randn(3).astype(np.float32)}}

    # corrupt upload: NaN leaf — rejected BEFORE it can touch the fold
    bad = {"params": {"w": np.full((4, 3), np.nan, np.float32),
                      "b": np.zeros((3,), np.float32)}}
    upload(4, bad, 7.0)
    assert server.rejected_uploads == 1 and server._agg_acc is None
    # stale upload (stamped for a round that isn't open): discarded
    upload(5, rand_tree(), 5.0, round_idx=3)
    assert server._agg_acc is None and not server.pending
    # three accepted uploads with uneven weights close the round (K=3);
    # nodes 4 and 5 end up spared
    trees, ns = [], [3.0, 5.0, 11.0]
    for sender, n in zip((1, 2, 3), ns):
        t = rand_tree()
        trees.append(t)
        upload(sender, t, n)
        if sender == 1:
            # a duplicate of an already-folded upload (chaos duplicate
            # fault) must NOT double-count into the running accumulator
            upload(1, trees[0], ns[0])
            assert server._agg_n == ns[0]
    assert server.round_idx == 1  # closed at the K-th report
    rec = server.round_log[-1]
    assert rec["participants"] == [1, 2, 3]
    assert rec.get("spared") == [4, 5]
    expected = treelib.tree_weighted_mean(trees, ns)
    for a, b in zip(jax.tree_util.tree_leaves(server.variables),
                    jax.tree_util.tree_leaves(expected)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # semantics unchanged vs the legacy buffered math
    total = sum(ns)
    legacy = treelib.tree_weighted_sum(trees, [n / total for n in ns])
    for a, b in zip(jax.tree_util.tree_leaves(server.variables),
                    jax.tree_util.tree_leaves(legacy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # a spare reporting after the close is stale-rejected, not folded
    upload(4, rand_tree(), 2.0, round_idx=0)
    assert not server.pending and server._agg_acc is None


def test_legacy_hotpath_matches_fast_inproc():
    """The legacy knobs (per-node unicast + buffered aggregation — the
    measurement baseline and old-peer interop mode) train to the same
    model as the default multicast + streaming path."""
    ds = synthetic_classification(
        num_train=120, num_test=30, input_shape=(16,), num_classes=4,
        num_clients=3, partition="homo", seed=3,
    )
    bundle = logistic_regression(16, 4)
    init = bundle.init(jax.random.PRNGKey(3))
    opt = make_client_optimizer("sgd", 0.1)
    lu = make_local_update(bundle, opt, 1)

    def run(multicast, streaming):
        bus = InprocBus()
        server = FedAvgServerManager(
            bus.register(0), init, num_clients=3, clients_per_round=3,
            comm_rounds=3, seed=3, multicast=multicast,
            streaming_agg=streaming,
        )
        for i in range(3):
            FedAvgClientManager(bus.register(i + 1), lu, ds, batch_size=16,
                                template_variables=init, seed=3)
        server.start()
        bus.drain()
        assert server.round_idx == 3
        return server.variables

    fast = run(True, True)
    legacy = run(False, False)
    for a, b in zip(jax.tree_util.tree_leaves(fast),
                    jax.tree_util.tree_leaves(legacy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_inproc_bus_unknown_receiver():
    bus = InprocBus()
    bus.register(0)
    with pytest.raises(KeyError):
        bus.route(Message("X", 0, 99))


def test_tcp_await_peers_timeout_midframe_kills_connection():
    """A readline that times out mid-frame leaves the buffered stream
    desynchronized (partial bytes discarded); the backend must close the
    connection instead of letting a retry parse the frame's tail
    (ADVICE r2, comm/tcp.py await_peers)."""
    import json as _json
    import socket as _socket
    import threading as _threading

    srv = _socket.create_server(("127.0.0.1", 0))
    host, port = srv.getsockname()

    def fake_hub():
        conn, _ = srv.accept()
        f = conn.makefile("rb")
        f.readline()  # registration hello
        conn.sendall((_json.dumps({"__hub__": "ack"}) + "\n").encode())
        f.readline()  # peers request
        # dribble HALF a frame, then stall past the client's budget
        conn.sendall(b'{"__hub__": "peers", "ids": [0')
        _threading.Event().wait(2.0)
        try:
            conn.close()
        except OSError:
            pass

    t = _threading.Thread(target=fake_hub, daemon=True)
    t.start()
    backend = TcpBackend(7, host, port)
    with pytest.raises(TimeoutError, match="connection closed"):
        backend.await_peers([0, 1], timeout=0.4)
    # the desynced socket is unusable from now on — no silent corruption
    with pytest.raises(OSError):
        backend.send_message(Message("X", 7, 0))
    assert backend._stopped.is_set()
    srv.close()


def test_server_deadline_zero_arrivals_and_stale_reply():
    """Round-deadline edges, inproc: (a) a deadline with ZERO arrivals
    closes the round with the global model unchanged; (b) a straggler's
    upload stamped with a closed round index is rejected, not folded
    into the current aggregation."""
    import time

    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg_cross_device import FedAvgServerManager
    from fedml_tpu.comm.inproc import InprocBus
    from fedml_tpu.comm.message import (MSG_ARG_KEY_MODEL_PARAMS,
                                        MSG_ARG_KEY_NUM_SAMPLES,
                                        MSG_ARG_KEY_ROUND_INDEX,
                                        MSG_TYPE_C2S_SEND_MODEL,
                                        tree_to_wire)

    bus = InprocBus()
    server_backend = bus.register(0)
    for i in (1, 2):
        bus.register(i)  # silent clients: never reply
    init = {"params": {"w": jnp.ones((2, 2))}}
    server = FedAvgServerManager(
        server_backend, init, num_clients=2, clients_per_round=2,
        comm_rounds=3, seed=0, round_timeout=0.15,
    )
    server.start()
    time.sleep(0.4)  # deadline fires with nobody arrived
    assert server.round_idx >= 1
    rec = server.round_log[0]
    assert rec["participants"] == [] and rec["dropped"] == [1, 2]
    assert server.zero_participant_rounds >= 1  # counted for loud failure
    np.testing.assert_array_equal(
        np.asarray(server.variables["params"]["w"]), np.ones((2, 2))
    )

    # stale reply: stamped round 0, but that round is closed.  Disarm
    # the deadline first — round_idx must not advance under us between
    # the read and the asserts (1-core box, GIL contention); the brief
    # sleep lets any in-flight timer callback drain (cancel() cannot
    # stop one that already started)
    server.round_timeout = None  # _arm_deadline becomes a no-op
    if server._deadline_timer is not None:
        server._deadline_timer.cancel()
    time.sleep(0.05)
    cur = server.round_idx
    stale = Message(MSG_TYPE_C2S_SEND_MODEL, 1, 0)
    stale.add_params(MSG_ARG_KEY_ROUND_INDEX, 0)
    stale.add_params(
        MSG_ARG_KEY_MODEL_PARAMS,
        tree_to_wire({"params": {"w": jnp.full((2, 2), 99.0)}}),
    )
    stale.add_params(MSG_ARG_KEY_NUM_SAMPLES, 5.0)
    server._on_model(stale)
    assert server.pending == {}  # rejected, not queued
    assert any("stale_from" in r for r in server.round_log)
    assert server.round_idx == cur
    np.testing.assert_array_equal(
        np.asarray(server.variables["params"]["w"]), np.ones((2, 2))
    )
    if server._deadline_timer is not None:
        server._deadline_timer.cancel()


def test_tcp_backend_auto_reconnect():
    """A client whose hub connection drops re-dials, re-registers (the
    hub's identity guard swaps the live conn), and keeps receiving —
    the r2 'nothing reconnects, nothing re-registers' gap."""
    import threading
    import time

    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    hub = TcpHub()
    recv = []
    client = TcpBackend(5, hub.host, hub.port, auto_reconnect=3)

    class Obs:
        def receive_message(self, t, m):
            recv.append(m.get("payload"))

    client.add_observer(Obs())
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    sender = TcpBackend(6, hub.host, hub.port)
    sender.await_peers([5])

    m1 = Message("X", 6, 5)
    m1.add_params("payload", "before")
    sender.send_message(m1)
    deadline = time.monotonic() + 5
    while "before" not in recv and time.monotonic() < deadline:
        time.sleep(0.02)
    assert "before" in recv

    # sever the hub-side connection for node 5 with shutdown(): a bare
    # close() is DEFERRED by the hub reader's makefile() io-ref (the
    # very gotcha _kill_connection documents) and would never drop the
    # conn — the test would pass vacuously on the original socket
    import socket as _socket

    old_conn = hub._conns[5]
    old_conn.sock.shutdown(_socket.SHUT_RDWR)
    # wait until the hub holds a NEW conn object for node 5 (the stale
    # entry lingers until its reader thread runs cleanup; await_peers
    # alone could observe the dead conn still registered and the test
    # would route m2 into it)
    deadline = time.monotonic() + 10
    while hub._conns.get(5) in (None, old_conn):
        assert time.monotonic() < deadline, "client never re-registered"
        time.sleep(0.02)
    m2 = Message("X", 6, 5)
    m2.add_params("payload", "after")
    sender.send_message(m2)
    deadline = time.monotonic() + 5
    while "after" not in recv and time.monotonic() < deadline:
        time.sleep(0.02)
    assert "after" in recv, "client did not survive the dropped connection"
    client.stop()
    sender.stop()
    hub.stop()


def test_trace_hops_end_to_end_over_hub():
    """ISSUE 6 tentpole pin: with tracing on, a frame delivered through
    the hub carries the full hop chain send → hub_in → hub_out → recv →
    done; the hub restamps the header per copy at drain time; dialing
    records a clock_sync offset estimate; and the sender's memoized
    frame encoding survives repeated (unicast + multicast) sends."""
    import time as _t

    from fedml_tpu.comm.backend import NodeManager
    from fedml_tpu.obs import trace_ctx
    from fedml_tpu.obs.telemetry import get_telemetry

    trace_ctx.set_enabled(True)
    get_telemetry().drain_events()
    hub = TcpHub()
    got = []

    class Mgr(NodeManager):
        def register_message_receive_handlers(self):
            self.register_message_receive_handler(
                "T", lambda m: got.append(m)
            )

    recv = TcpBackend(1, hub.host, hub.port)
    Mgr(recv)
    recv.run_in_thread()
    sender = TcpBackend(2, hub.host, hub.port)
    try:
        sender.await_peers([1])
        m = Message("T", 2, 1)
        m.add_params("model", np.arange(1000, dtype=np.float32))
        m.add_params("round_idx", 4)
        sender.send_message(m)
        memo = m._frame_parts
        assert memo is not None
        sender.send_multicast(m, [1])  # native hub fan-out, same message
        assert m._frame_parts is memo  # encode-once survives stamping
        deadline = _t.time() + 10
        while len(got) < 2 and _t.time() < deadline:
            _t.sleep(0.01)
        _t.sleep(0.2)  # let the 2nd handler's 'done' stamp land
        assert len(got) == 2
        for g in got:
            ctx = g.params[trace_ctx.TRACE_KEY]
            assert [h[1] for h in ctx["hops"]] \
                == ["send", "hub_in", "hub_out", "recv", "done"]
            assert ctx["rnd"] == 4
            # stamps are monotone along the chain (one box, one clock
            # family; cross-process skew is what clock_sync corrects)
            ts = [h[2] for h in ctx["hops"]]
            assert ts == sorted(ts)
        evs = get_telemetry().drain_events()
        assert sum(e["kind"] == "trace_hop" for e in evs) == 2
        syncs = [e for e in evs if e["kind"] == "clock_sync"]
        assert {e["node"] for e in syncs} >= {1, 2}
        for e in syncs:
            assert e["rtt_s"] >= 0 and abs(e["offset_s"]) < 1.0
    finally:
        sender.stop()
        recv.stop()
        hub.stop()
        trace_ctx.set_enabled(None)
        get_telemetry().drain_events()


# --- striped fan-out + decode/fold pipeline (ISSUE 8) -----------------------


def _hub_federation(*, stripe_bytes, decode_workers, codec="none", seed=1,
                    rounds=3, num_clients=3, input_dim=64):
    """One in-process federation over a real TcpHub; returns (final
    model leaf bytes, per-client upload digests, hub stats)."""
    ds = synthetic_classification(
        num_train=120, num_test=30, input_shape=(input_dim,),
        num_classes=2, num_clients=num_clients, partition="homo", seed=seed,
    )
    bundle = logistic_regression(input_dim, 2)
    init = bundle.init(jax.random.PRNGKey(seed))
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), 1)
    hub = TcpHub(stripe_bytes=stripe_bytes, max_inflight_stripes=2)
    sb = TcpBackend(0, hub.host, hub.port)
    cbs = [TcpBackend(i + 1, hub.host, hub.port) for i in range(num_clients)]
    server = FedAvgServerManager(
        sb, init, num_clients=num_clients, clients_per_round=num_clients,
        comm_rounds=rounds, seed=seed, codec=codec,
        decode_workers=decode_workers,
    )
    clients = [
        FedAvgClientManager(cb, lu, ds, batch_size=16,
                            template_variables=init, seed=seed)
        for cb in cbs
    ]
    threads = [cb.run_in_thread() for cb in cbs]
    st = sb.run_in_thread()
    server.start()
    st.join(timeout=90)
    assert not st.is_alive(), "server did not finish"
    for t in threads:
        t.join(timeout=15)
    stats = hub.stats()
    hub.stop()
    assert server.round_idx == rounds
    leaves = [np.asarray(l).tobytes()
              for l in jax.tree_util.tree_leaves(server.variables)]
    return leaves, [c.upload_digest for c in clients], stats


def test_striped_multicast_reassembles_byte_identical():
    """A striped mcast reaches every receiver byte-identical to the
    whole frame: stripes carry crcs, receivers reassemble, and the
    payload is still shipped to the hub exactly once."""
    import time

    from fedml_tpu.obs.telemetry import get_telemetry

    hub = TcpHub(stripe_bytes=64 << 10, max_inflight_stripes=2)
    got = {1: [], 2: [], 3: []}

    class Obs:
        def __init__(self, i):
            self.i = i

        def receive_message(self, t, m):
            got[self.i].append(m)

    receivers = []
    for i in (1, 2, 3):
        b = TcpBackend(i, hub.host, hub.port)
        b.add_observer(Obs(i))
        b.run_in_thread()
        receivers.append(b)
    sender = TcpBackend(9, hub.host, hub.port)
    sender.await_peers([1, 2, 3])
    payload = np.arange(300_000, dtype=np.float32)  # 1.2 MB -> 19 stripes
    m = Message("MCAST_PIN", 9, -1)
    m.add_params("model", payload)
    before = get_telemetry().snapshot()["counters"]
    sender.send_multicast(m, [1, 2, 3])
    deadline = time.monotonic() + 15
    while any(not got[i] for i in (1, 2, 3)) and time.monotonic() < deadline:
        time.sleep(0.02)
    for i in (1, 2, 3):
        assert got[i], f"node {i} never received the striped multicast"
        np.testing.assert_array_equal(np.asarray(got[i][0].get("model")),
                                      payload)
    after = get_telemetry().snapshot()["counters"]
    key = "comm.sent_bytes{msg_type=MCAST_PIN}"
    # encode-once broadcast still holds: ONE payload to the hub
    assert payload.nbytes <= after.get(key, 0) - before.get(key, 0) \
        < 2 * payload.nbytes
    stats = hub.stats()
    n_stripes = -(-payload.nbytes // (64 << 10)) + 1  # chunks + header pad
    assert stats["striped_mcasts"] == 1
    # 3 receivers x ceil(payload/stripe) frames (untraced: no extra
    # header stripe)
    assert stats["stripe_frames"] == 3 * (n_stripes - 1) \
        or stats["stripe_frames"] == 3 * n_stripes
    key = "comm.stripe_reassemblies{msg_type=MCAST_PIN}"
    assert after.get(key, 0) - before.get(key, 0) == 3
    for b in receivers:
        b.stop()
    sender.stop()
    hub.stop()


def _stripe_fault_rig(hook):
    """One sender -> hub(striped) -> one hooked receiver; returns
    (send(msg_type, nbytes), got list, closer)."""
    import time

    hub = TcpHub(stripe_bytes=16 << 10, max_inflight_stripes=2)
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append(m)

    recv = TcpBackend(1, hub.host, hub.port)
    recv.add_observer(Obs())
    recv.set_stripe_fault_hook(hook)
    recv.run_in_thread()
    sender = TcpBackend(2, hub.host, hub.port)
    sender.await_peers([1])

    def send(tag, nfloats):
        m = Message(tag, 2, 1)
        m.add_params("model", np.arange(nfloats, dtype=np.float32))
        sender.send_multicast(m, [1])

    def wait(n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while len(got) < n and time.monotonic() < deadline:
            time.sleep(0.02)

    def close():
        sender.stop()
        recv.stop()
        hub.stop()

    return send, wait, got, close


def test_stripe_gap_kills_logical_frame_not_connection():
    """A lost stripe must cost exactly its logical frame: the
    reassembly aborts (counted), the connection survives, and the NEXT
    frame arrives intact."""
    from fedml_tpu.obs.telemetry import get_telemetry

    state = {"n": 0}

    def drop_second_stripe(mt, sid, idx, chunk):
        if mt == "VICTIM" and idx == 1 and state["n"] == 0:
            state["n"] += 1
            return None  # swallowed: the reassembler sees a gap
        return chunk

    before = get_telemetry().snapshot()["counters"]
    send, wait, got, close = _stripe_fault_rig(drop_second_stripe)
    try:
        send("VICTIM", 20_000)   # 80 KB -> 5 stripes, stripe 1 dropped
        send("SURVIVOR", 20_000)
        wait(1)
        assert [m.type for m in got] == ["SURVIVOR"]
        np.testing.assert_array_equal(
            np.asarray(got[0].get("model")),
            np.arange(20_000, dtype=np.float32))
        after = get_telemetry().snapshot()["counters"]
        key = "comm.stripe_aborts{msg_type=VICTIM,reason=gap}"
        assert after.get(key, 0) - before.get(key, 0) == 1
    finally:
        close()


def test_stripe_crc_catches_corruption():
    """A corrupted stripe fails its crc32: the logical frame dies
    (counted, reason=crc), nothing garbled is ever delivered, and the
    stream keeps flowing."""
    from fedml_tpu.obs.telemetry import get_telemetry

    state = {"n": 0}

    def corrupt_first(mt, sid, idx, chunk):
        if mt == "VICTIM" and state["n"] == 0:
            state["n"] += 1
            bad = bytearray(chunk)
            bad[0] ^= 0xFF
            return bytes(bad)
        return chunk

    before = get_telemetry().snapshot()["counters"]
    send, wait, got, close = _stripe_fault_rig(corrupt_first)
    try:
        send("VICTIM", 20_000)
        send("SURVIVOR", 20_000)
        wait(1)
        assert [m.type for m in got] == ["SURVIVOR"]
        after = get_telemetry().snapshot()["counters"]
        key = "comm.stripe_aborts{msg_type=VICTIM,reason=crc}"
        assert after.get(key, 0) - before.get(key, 0) == 1
    finally:
        close()


def test_striped_traced_hop_chain_has_reasm_stamp():
    """Tracing over the striped path: the hub restamps hub_out on the
    per-receiver stripe-0 drain, and the receiver backdates a ``reasm``
    hop to first-stripe arrival — the chain fed_timeline splits
    bcast_deliver/stripe_reasm on."""
    import time as _t

    from fedml_tpu.comm.backend import NodeManager
    from fedml_tpu.obs import trace_ctx

    trace_ctx.set_enabled(True)
    hub = TcpHub(stripe_bytes=16 << 10)
    got = []

    class Mgr(NodeManager):
        def register_message_receive_handlers(self):
            self.register_message_receive_handler(
                "T", lambda m: got.append(m)
            )

    recv = TcpBackend(1, hub.host, hub.port)
    Mgr(recv)
    recv.run_in_thread()
    sender = TcpBackend(2, hub.host, hub.port)
    try:
        sender.await_peers([1])
        m = Message("T", 2, 1)
        m.add_params("model", np.arange(20_000, dtype=np.float32))
        m.add_params("round_idx", 7)
        sender.send_multicast(m, [1])
        deadline = _t.time() + 10
        while not got and _t.time() < deadline:
            _t.sleep(0.01)
        _t.sleep(0.2)  # let the 'done' stamp land
        assert got
        ctx = got[0].params[trace_ctx.TRACE_KEY]
        assert [h[1] for h in ctx["hops"]] \
            == ["send", "hub_in", "hub_out", "reasm", "recv", "done"]
        ts = [h[2] for h in ctx["hops"]]
        assert ts == sorted(ts)  # reasm backdated, still monotone
    finally:
        sender.stop()
        recv.stop()
        hub.stop()
        trace_ctx.set_enabled(None)


def test_sender_pool_pacing_single_worker_interleaves_receivers():
    """Every receiver of a striped fan-out streams: the hub's one loop
    drains every receiver's head stripe before any tail, then each
    receiver's tails until its socket would block, so no receiver
    starves behind another's whole sequence."""
    import time

    hub = TcpHub(stripe_bytes=8 << 10, max_inflight_stripes=1)
    got = {1: [], 2: [], 3: []}

    class Obs:
        def __init__(self, i):
            self.i = i

        def receive_message(self, t, m):
            got[self.i].append(m)

    receivers = []
    for i in (1, 2, 3):
        b = TcpBackend(i, hub.host, hub.port)
        b.add_observer(Obs(i))
        b.run_in_thread()
        receivers.append(b)
    sender = TcpBackend(9, hub.host, hub.port)
    sender.await_peers([1, 2, 3])
    payload = np.arange(50_000, dtype=np.float32)  # 200 KB -> 25 stripes
    m = Message("PACE", 9, -1)
    m.add_params("model", payload)
    sender.send_multicast(m, [1, 2, 3])
    deadline = time.monotonic() + 15
    while any(not got[i] for i in (1, 2, 3)) and time.monotonic() < deadline:
        time.sleep(0.02)
    for i in (1, 2, 3):
        assert got[i], f"node {i} starved during the striped fan-out"
        np.testing.assert_array_equal(np.asarray(got[i][0].get("model")),
                                      payload)
    for b in receivers:
        b.stop()
    sender.stop()
    hub.stop()


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_striped_pipelined_federation_bit_identical(codec):
    """THE determinism pin for ISSUE 8: striped fan-out + off-thread
    decode/fold + double-buffered encode produce byte-identical final
    models AND byte-identical client upload digests vs the whole-frame
    serial baseline, fp32 and int8+EF (the fp64 num/den streaming fold
    is exact at these magnitudes, so fold order cannot leak into the
    bits)."""
    base = _hub_federation(stripe_bytes=0, decode_workers=0, codec=codec)
    fast = _hub_federation(stripe_bytes=256, decode_workers=2, codec=codec)
    assert fast[2]["striped_mcasts"] >= 1
    assert fast[2]["stripe_frames"] > 0
    assert base[0] == fast[0], "final model bits differ striped vs whole"
    assert base[1] == fast[1], "upload digests differ striped vs whole"


def test_oversize_mcast_falls_back_to_whole_frame():
    """A multicast frame larger than half the receiver reassembly
    budget is NOT striped — striping it would overflow-abort on every
    client and the cohort would never sync (round after round of
    zero-participant closes).  The hub ships it whole instead:
    functional beats fast."""
    import time

    from fedml_tpu.comm.tcp import _MAX_REASM_BYTES

    hub = TcpHub(stripe_bytes=64 << 10, max_inflight_stripes=2)
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append(m)

    rx = TcpBackend(1, hub.host, hub.port)
    rx.add_observer(Obs())
    rx.run_in_thread()
    sender = TcpBackend(9, hub.host, hub.port)
    sender.await_peers([1])
    n = _MAX_REASM_BYTES // 2 // 4 + 1024  # just over the stripe cap
    payload = np.arange(n, dtype=np.float32)
    m = Message("MCAST_BIG", 9, -1)
    m.add_params("model", payload)
    sender.send_multicast(m, [1])
    deadline = time.monotonic() + 30
    while not got and time.monotonic() < deadline:
        time.sleep(0.05)
    assert got, "oversize multicast never delivered"
    np.testing.assert_array_equal(np.asarray(got[0].get("model")), payload)
    stats = hub.stats()
    assert stats["striped_mcasts"] == 0 and stats["stripe_frames"] == 0
    rx.stop()
    sender.stop()
    hub.stop()


def test_stale_partial_stream_evicted_on_byte_pressure():
    """A partial stripe stream whose final never arrives (hub reconnect
    killed its tail mid-broadcast) must not hold the reassembly byte
    budget forever: when a LIVE stream needs the bytes, the stale one
    is evicted (counted reason=stale) and the live broadcast still
    assembles — one outage costs one frame, never all future ones."""
    import zlib as _zlib

    from fedml_tpu.obs.telemetry import get_telemetry

    hub = TcpHub()
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append(m)

    b = TcpBackend(1, hub.host, hub.port)
    b.add_observer(Obs())
    b.run_in_thread()
    try:
        budget = b._MAX_REASM_BYTES

        def stripe(sid, i, n, chunk):
            return ({"sid": sid, "i": i, "n": n, "msg_type": "BIG",
                     "crc": _zlib.crc32(chunk)}, chunk)

        # stale stream: one stripe of budget-32 bytes, final never comes
        big = b"\x00" * (budget - 32)
        f, c = stripe(101, 0, 2, big)
        b._on_stripe(f, c, nbytes=len(c))
        assert b._reasm_bytes == len(big)
        # live stream: a small real frame that does NOT fit the residue
        m = Message("BIG", 9, -1)
        m.add_params("w", np.arange(64, dtype=np.float32))
        frame = m.to_frame()
        half = len(frame) // 2
        before = get_telemetry().snapshot()["counters"]
        for i, chunk in enumerate((frame[:half], frame[half:])):
            f, c = stripe(202, i, 2, chunk)
            b._on_stripe(f, c, nbytes=len(c))
        after = get_telemetry().snapshot()["counters"]
        assert got and np.asarray(got[0].get("w")).shape == (64,)
        key = "comm.stripe_aborts{msg_type=BIG,reason=stale}"
        assert after.get(key, 0) - before.get(key, 0) == 1
        assert 101 not in b._reasm and b._reasm_bytes == 0
    finally:
        b.stop()
        hub.stop()
