"""Reactor hub data plane (PR 20): the streaming frame parser, pooled
refcounted payload buffers, one-loop-thread behaviors, the
dead-receiver/rebind/partial-write pin-release contract, and the
high-connection accept/churn soak.

Fast tests drive the parser/pool against hand-torn byte streams (no
sockets) and a real reactor ``TcpHub`` over loopback with hand-rolled
dialers; the 512-connection soak is marked slow (tier-2)."""

import json
import socket
import threading
import time

import pytest

from fedml_tpu.comm.message import (
    FRAME_BINLEN_KEY,
    HUB_KEY,
    Message,
    SHM_SEQ_KEY,
)
from fedml_tpu.comm.mux import TcpMuxBackend
from fedml_tpu.comm.reactor import BufPool, FrameError, FrameParser
from fedml_tpu.comm.tcp import TcpBackend, TcpHub


def _wait(cond, timeout=15.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert cond(), "condition never held"


class _Collect:
    def __init__(self, sink, key):
        self.sink, self.key = sink, key

    def receive_message(self, t, m):
        self.sink.setdefault(self.key, []).append(m)


# -- FrameParser: torn / pipelined / hostile byte streams --------------------

def _drive(parser, stream, chunk_sizes):
    """Feed ``stream`` through the parser in chunks of the given sizes
    (cycling), via the recv_into contract a reactor socket uses."""
    frames = []
    pos = 0
    i = 0
    while pos < len(stream):
        n = chunk_sizes[i % len(chunk_sizes)]
        i += 1
        target = parser.recv_target()
        take = min(n, len(target), len(stream) - pos)
        target[:take] = stream[pos:pos + take]
        pos += take
        frames.extend(parser.consumed(take))
    return frames


def _frame_bytes(hdr, payload=b""):
    h = dict(hdr)
    if payload:
        h[FRAME_BINLEN_KEY] = len(payload)
    return (json.dumps(h) + "\n").encode() + payload


def test_parser_whole_frames_single_read():
    p = FrameParser()
    stream = _frame_bytes({"a": 1}) + _frame_bytes({"b": 2}, b"xyz")
    frames = _drive(p, stream, [len(stream)])
    assert len(frames) == 2
    (h1, l1, pay1, r1), (h2, l2, pay2, r2) = frames
    assert h1 == {"a": 1} and pay1 == b"" and r1 is None
    assert h2["b"] == 2 and bytes(pay2) == b"xyz" and r2 is not None
    r2.release()


def test_parser_torn_header_across_reads():
    p = FrameParser()
    stream = _frame_bytes({"msg_type": "T", "receiver": 7})
    # 1-byte reads: the header accumulates byte by byte
    frames = _drive(p, stream, [1])
    assert len(frames) == 1
    hdr, line, payload, region = frames[0]
    assert hdr["receiver"] == 7 and line == stream and region is None


def test_parser_torn_payload_across_reads():
    p = FrameParser()
    payload = bytes(range(256)) * 64  # 16 KiB
    stream = _frame_bytes({"receiver": 1}, payload)
    frames = _drive(p, stream, [7, 64, 4096])
    assert len(frames) == 1
    hdr, line, got, region = frames[0]
    assert bytes(got) == payload
    assert region is not None
    region.release()


def test_parser_pipelined_frames_one_read():
    p = FrameParser()
    stream = b"".join(
        _frame_bytes({"receiver": i}, bytes([i]) * (100 + i))
        for i in range(5))
    frames = _drive(p, stream, [len(stream)])
    assert [f[0]["receiver"] for f in frames] == list(range(5))
    for f in frames:
        assert bytes(f[2]) == bytes([f[0]["receiver"]]) * \
            (100 + f[0]["receiver"])
        f[3].release()


def test_parser_payload_prefix_in_header_chunk():
    # header + half the payload in one read, the rest in the next:
    # exercises the one scratch->region prefix copy
    p = FrameParser()
    payload = b"P" * 1000
    stream = _frame_bytes({"receiver": 3}, payload)
    cut = stream.find(b"\n") + 1 + 500
    frames = _drive(p, stream[:cut], [cut]) + \
        _drive(p, stream[cut:], [len(stream) - cut])
    assert len(frames) == 1
    assert bytes(frames[0][2]) == payload
    frames[0][3].release()


def test_parser_doorbell_frames_are_header_only():
    # shm doorbell: __binlen__ bytes live in the slab, not the stream
    p = FrameParser()
    stream = _frame_bytes({SHM_SEQ_KEY: 4, FRAME_BINLEN_KEY: 999}) + \
        _frame_bytes({"receiver": 1})
    frames = _drive(p, stream, [len(stream)])
    assert len(frames) == 2
    assert frames[0][0][SHM_SEQ_KEY] == 4
    assert frames[0][2] == b"" and frames[0][3] is None


def test_parser_oversize_header_fatal():
    p = FrameParser(max_header_bytes=1024)
    with pytest.raises(FrameError):
        _drive(p, b"x" * 4096, [512])


def test_parser_garbled_header_fatal():
    p = FrameParser()
    with pytest.raises(FrameError):
        _drive(p, b"not json at all\n", [16])
    p2 = FrameParser()
    with pytest.raises(FrameError):
        _drive(p2, b"[1, 2, 3]\n", [10])  # JSON, but not an object


def test_parser_binary_garbage_header_fatal():
    # non-UTF-8 bytes make json.loads raise UnicodeDecodeError — a
    # ValueError sibling, NOT a JSONDecodeError subclass — which must
    # hit the same connection-fatal FrameError path as ASCII garbage
    p = FrameParser()
    with pytest.raises(FrameError):
        _drive(p, b"\x80\x81\x82\n", [4])


def test_parser_binary_garbage_releases_inflight_regions():
    # the _fatal cleanup contract must hold for the UnicodeDecodeError
    # path too: a completed-but-undelivered frame's pooled region is
    # released, never leaked
    pool = BufPool()
    p = FrameParser(pool=pool)
    stream = _frame_bytes({"receiver": 1}, b"z" * 64) + b"\xff\xfe\n"
    with pytest.raises(FrameError):
        _drive(p, stream, [len(stream)])
    assert pool.live == 0


@pytest.mark.parametrize("binlen", ["not-a-number", -5, [1], 1 << 62])
def test_parser_bad_binlen_fatal(binlen):
    # __binlen__ comes off the wire: non-numeric (ValueError/TypeError
    # in int()), negative (broken PAYLOAD slice state), and absurd
    # (MemoryError inside pool.acquire) values must all die as
    # FrameError under the parser's own fatal policy
    pool = BufPool()
    p = FrameParser(pool=pool)
    line = (json.dumps({"receiver": 1, FRAME_BINLEN_KEY: binlen})
            + "\n").encode()
    with pytest.raises(FrameError):
        _drive(p, line, [len(line)])
    assert pool.live == 0


def test_parser_binlen_zero_string_is_header_only():
    # "0" is truthy but announces zero payload bytes: same as an
    # absent binlen — a header-only frame, no pooled region
    p = FrameParser()
    line = (json.dumps({"receiver": 1, FRAME_BINLEN_KEY: "0"})
            + "\n").encode()
    frames = _drive(p, line, [len(line)])
    assert len(frames) == 1
    assert frames[0][2] == b"" and frames[0][3] is None


def test_parser_fatal_releases_inflight_regions():
    # a garbled header after a completed-payload frame in the same
    # chunk must not leak the completed frame's pooled region
    pool = BufPool()
    p = FrameParser(pool=pool)
    stream = _frame_bytes({"receiver": 1}, b"z" * 64) + b"garbage\n"
    with pytest.raises(FrameError):
        _drive(p, stream, [len(stream)])
    assert pool.live == 0


def test_bufpool_reuse_and_live_accounting():
    pool = BufPool()
    r1 = pool.acquire(5000)
    assert pool.live == 1
    buf_id = id(r1._buf)
    r1.retain()
    r1.release()
    assert pool.live == 1  # still one outstanding reference
    r1.release()
    assert pool.live == 0
    r2 = pool.acquire(6000)  # same 8 KiB size class: freelist hit
    assert id(r2._buf) == buf_id and pool.reuses == 1
    r2.release()


def test_parser_close_releases_partial_payload():
    pool = BufPool()
    p = FrameParser(pool=pool)
    stream = _frame_bytes({"receiver": 1}, b"q" * 5000)
    _drive(p, stream[:200], [200])  # mid-payload
    assert pool.live == 1
    p.close()
    assert pool.live == 0


# -- reactor hub over loopback -----------------------------------------------

def _dial_raw(host, port, node_id, timeout=10.0, rcvbuf=None):
    """Hand-rolled minimal dialer: hello v1 + ping_done, no reader
    thread.  Returns the connected socket (registered at the hub).
    ``rcvbuf`` sizes SO_RCVBUF before connecting (the window a
    non-reading receiver offers)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(timeout)
    s.connect((host, port))
    f = s.makefile("rb")
    s.sendall((json.dumps({"node_id": node_id}) + "\n").encode())
    ack = json.loads(f.readline())
    assert ack.get(HUB_KEY) == "ack"
    s.sendall((json.dumps({HUB_KEY: "ping_done"}) + "\n").encode())
    f.close()
    return s


def test_reactor_is_default_and_single_threaded():
    hub = TcpHub()
    socks = []
    try:
        for i in range(64):
            socks.append(_dial_raw(hub.host, hub.port, 100 + i))
        _wait(lambda: hub.stats()["connections"] == 64)
        snap = hub.stats()
        assert snap["threads"] == 1
        # selector watches server + wakeup pipe + every conn
        assert snap["open_fds"] == 64 + 2
    finally:
        for s in socks:
            s.close()
        hub.stop()


def test_reactor_handshake_clock_sync_pongs():
    hub = TcpHub()
    try:
        s = socket.create_connection((hub.host, hub.port), timeout=10)
        f = s.makefile("rb")
        s.sendall((json.dumps({"node_id": 5}) + "\n").encode())
        assert json.loads(f.readline()).get(HUB_KEY) == "ack"
        for k in range(3):
            s.sendall((json.dumps(
                {HUB_KEY: "ping", "t0": 100.0 + k}) + "\n").encode())
            pong = json.loads(f.readline())
            assert pong[HUB_KEY] == "pong" and pong["t0"] == 100.0 + k
        s.sendall((json.dumps({HUB_KEY: "ping_done"}) + "\n").encode())
        _wait(lambda: hub.stats()["nodes"] == 1)
        f.close()
        s.close()
    finally:
        hub.stop()


def test_reactor_garbled_header_drops_conn_only():
    hub = TcpHub()
    try:
        good = _dial_raw(hub.host, hub.port, 1)
        bad = _dial_raw(hub.host, hub.port, 2)
        _wait(lambda: hub.stats()["connections"] == 2)
        bad.sendall(b"this is not a frame\n")
        _wait(lambda: hub.stats()["connections"] == 1)
        # the loop (and the good conn) survived the hostile peer
        assert hub.stats()["threads"] == 1
        good.close()
        bad.close()
    finally:
        hub.stop()


def test_reactor_binary_garbage_drops_conn_only():
    """Regression: non-UTF-8 bytes with a newline used to raise
    UnicodeDecodeError past the FrameError handler and kill the single
    reactor thread — wedging EVERY connection on the hub.  The hostile
    conn must die alone and the loop must keep accepting."""
    hub = TcpHub()
    socks = []
    try:
        good = _dial_raw(hub.host, hub.port, 1)
        bad = _dial_raw(hub.host, hub.port, 2)
        socks += [good, bad]
        _wait(lambda: hub.stats()["connections"] == 2)
        bad.sendall(b"\x80\x81\x82\n")
        _wait(lambda: hub.stats()["connections"] == 1)
        assert hub.stats()["threads"] == 1
        # the loop survived: a fresh dial still registers
        socks.append(_dial_raw(hub.host, hub.port, 3))
        _wait(lambda: hub.stats()["connections"] == 2)
    finally:
        for s in socks:
            s.close()
        hub.stop()


def test_reactor_hostile_binlen_drops_conn_only():
    # a valid-JSON header announcing an absurd __binlen__ must not
    # become a MemoryError in the event loop: connection-fatal, loop
    # and cohort survive
    hub = TcpHub()
    socks = []
    try:
        good = _dial_raw(hub.host, hub.port, 1)
        bad = _dial_raw(hub.host, hub.port, 2)
        socks += [good, bad]
        _wait(lambda: hub.stats()["connections"] == 2)
        bad.sendall((json.dumps(
            {"msg_type": "X", FRAME_BINLEN_KEY: 1 << 60}) + "\n"
        ).encode())
        _wait(lambda: hub.stats()["connections"] == 1)
        assert hub.stats()["threads"] == 1
    finally:
        for s in socks:
            s.close()
        hub.stop()


def test_reactor_rebind_kills_already_queued_frames_for_stolen_id(
        monkeypatch):
    """The rebind policy holds for IN-FLIGHT frames too: a
    frame still QUEUED for an id when the id rebinds to a newer conn is
    dropped at drain (counted), never delivered to the displaced owner.
    The drain visit for the target conn is held off (not blocked — the
    loop keeps servicing everything else) until after the rebind."""
    from fedml_tpu.comm import tcp as tcp_mod

    gate = threading.Event()
    real_drain = tcp_mod.TcpHub._drain_conn
    hub = TcpHub()
    held = []

    def gated_drain(self, st, heads_only=False):
        if self is hub and not gate.is_set():
            with self._lock:
                holding = any(e[0] == "QF" for e in st.frames)
            if holding:
                if st not in held:
                    held.append(st)
                return
        return real_drain(self, st, heads_only)

    got = {}
    mux = claimer = sender = None
    try:
        monkeypatch.setattr(tcp_mod.TcpHub, "_drain_conn", gated_drain)
        mux = TcpMuxBackend([1, 2], hub.host, hub.port)
        for i in (1, 2):
            mux.virtual(i).add_observer(_Collect(got, i))
        mux.run_in_thread()
        sender = TcpBackend(9, hub.host, hub.port)
        sender.await_peers([1, 2])
        m2 = Message("QF", 9, 2)
        m2.add_params("x", 2)
        sender.send_message(m2)  # parks in the mux conn's queue
        _wait(lambda: len(held) == 1)
        claimer = TcpBackend(2, hub.host, hub.port)  # rebinds id 2
        claimer.add_observer(_Collect(got, "claimer"))
        claimer.run_in_thread()
        _wait(lambda: hub.stats()["node_rebinds"] == 1)
        gate.set()
        hub._wake(held[0])  # re-offer the held conn to the loop
        _wait(lambda: hub.stats()["dropped_frames"].get("QF", 0) == 1)
        time.sleep(0.2)
        # neither the displaced muxer nor the new owner got THAT copy
        assert not got.get(2)
        assert not got.get("claimer")
    finally:
        gate.set()
        for b in (mux, claimer, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_rebind_soak_releases_every_queued_pin():
    """Satellite leak contract: soak rebinds of one id with pinned
    entries still queued on the displaced conn — every pin must be
    released (drained, dropped, or flushed at cleanup/stop) and the
    outstanding-pin count must return to 0."""

    class _Pin:
        lives = 0
        lock = threading.Lock()

        def __init__(self):
            with _Pin.lock:
                _Pin.lives += 1
            self._refs = 1

        def retain(self):
            with _Pin.lock:
                _Pin.lives += 1
            self._refs += 1

        def release(self):
            with _Pin.lock:
                _Pin.lives -= 1

    hub = TcpHub()
    payload = b"p" * 2048
    line = (json.dumps(
        {"msg_type": "LK", FRAME_BINLEN_KEY: len(payload)}) + "\n"
    ).encode()
    socks = []
    try:
        for i in range(200):
            s = _dial_raw(hub.host, hub.port, 7)
            socks.append(s)
            if i:
                _wait(lambda: hub.stats()["node_rebinds"] >= i)
            # queue pinned entries on the CURRENT owner; the next dial
            # displaces it (some entries drain, some die queued — every
            # path must release)
            for _ in range(3):
                pin = _Pin()
                hub._forward(7, (line, payload), msg_type="LK",
                             region=pin)
                pin.release()  # the enqueuer's own reference
        hub.stop()  # flushes whatever is still queued
        assert _Pin.lives == 0
        assert hub._bufpool.live == 0
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        hub.stop()


@pytest.mark.parametrize("teardown", ["peer_closes", "hub_stops"])
def test_partial_write_pin_released_on_teardown(teardown):
    """A frame larger than the socket buffers, routed to a receiver
    that stops reading, parks its conn on EVENT_WRITE mid-frame with
    the payload's pooled region held as ``wregion``.  Whether the
    receiver's socket closes or the hub stops, the teardown must
    release that pin: ``_bufpool.live`` returns to 0."""
    hub = TcpHub()
    stopped = False
    socks = []
    try:
        recv = _dial_raw(hub.host, hub.port, 5, rcvbuf=4096)
        sender = _dial_raw(hub.host, hub.port, 9)
        socks += [recv, sender]
        _wait(lambda: hub.stats()["connections"] == 2)
        st = hub._conns[5]
        # larger than everything the kernel can hold in flight (the
        # hub's send buffer plus the receiver's small window), so the
        # drain must stop part-way through the frame
        sndbuf = st.sock.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF)
        payload = b"w" * (sndbuf + (4 << 20))
        sender.sendall(_frame_bytes(
            {"msg_type": "PW", "receiver": 5}, payload))
        _wait(lambda: st.want_write and st.wregion is not None)
        assert st.wpend  # parked mid-frame, bytes still to write
        assert hub._bufpool.live == 1
        assert hub.stats()["threads"] == 1
        if teardown == "peer_closes":
            recv.close()
            _wait(lambda: hub.stats()["connections"] == 1)
            assert hub.stats()["threads"] == 1
        else:
            hub.stop()
            stopped = True
        _wait(lambda: hub._bufpool.live == 0)
        assert st.wregion is None and st.dead
    finally:
        for s in socks:
            s.close()
        if not stopped:
            hub.stop()


@pytest.mark.slow
def test_512_conn_accept_churn_soak():
    """The scaling claim, measured: 512 live connections on ONE loop
    thread, accept latency
    flat through churn, and no pooled-buffer leak after 3 churn waves
    of 128 closes + 128 re-dials."""
    hub = TcpHub()
    socks = {}
    try:
        lat = []
        for i in range(512):
            t0 = time.perf_counter()
            socks[i] = _dial_raw(hub.host, hub.port, 1000 + i)
            lat.append(time.perf_counter() - t0)
        _wait(lambda: hub.stats()["connections"] == 512, timeout=60)
        snap = hub.stats()
        assert snap["threads"] == 1  # the O(1) bar (<= 8 in the issue)
        assert snap["open_fds"] == 512 + 2
        lat.sort()
        base_p50 = lat[len(lat) // 2]
        for wave in range(3):
            for i in range(wave * 128, wave * 128 + 128):
                socks.pop(i).close()
            _wait(lambda: hub.stats()["connections"] == 384,
                  timeout=60)
            churn_lat = []
            for i in range(wave * 128, wave * 128 + 128):
                t0 = time.perf_counter()
                socks[i] = _dial_raw(hub.host, hub.port, 1000 + i)
                churn_lat.append(time.perf_counter() - t0)
            _wait(lambda: hub.stats()["connections"] == 512,
                  timeout=60)
            churn_lat.sort()
            # accept latency under churn stays the same order as the
            # cold fill (generous 20x bound: this is a leak/cliff
            # detector, not a microbenchmark)
            assert churn_lat[len(churn_lat) // 2] < max(
                base_p50 * 20, 0.25)
        assert hub.stats()["threads"] == 1
        assert hub._bufpool.live == 0
    finally:
        for s in socks.values():
            try:
                s.close()
            except OSError:
                pass
        hub.stop()
