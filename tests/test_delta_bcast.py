"""Delta/dedup broadcast tests (fedavg_cross_device ``bcast='delta'``):
chain byte-identity pins, ack grouping, stale-base eviction, resync
recovery, and the mux/lane compositions."""

import os
import time

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.fedavg_cross_device import (
    FedAvgClientManager,
    FedAvgServerManager,
    apply_bcast_delta,
    encode_bcast_delta,
)
from fedml_tpu.comm.backend import CommBackend
from fedml_tpu.comm.inproc import InprocBus
from fedml_tpu.comm.message import (
    MSG_ARG_KEY_DELTA_BASE,
    MSG_ARG_KEY_MODEL_PARAMS,
    MSG_ARG_KEY_ROUND_INDEX,
    MSG_TYPE_C2S_RESYNC,
    MSG_TYPE_S2C_SYNC_MODEL,
    Message,
    tree_from_wire,
)
from fedml_tpu.core.client import make_client_optimizer, make_local_update
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models.linear import logistic_regression
from fedml_tpu.obs.telemetry import get_telemetry


def _counters():
    return dict(get_telemetry().snapshot()["counters"])


def _problem(seed=1, num_clients=2):
    ds = synthetic_classification(
        num_train=60 * num_clients, num_test=30, input_shape=(8,),
        num_classes=2, num_clients=num_clients, partition="homo", seed=seed,
    )
    bundle = logistic_regression(8, 2)
    init = bundle.init(jax.random.PRNGKey(seed))
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), 1)
    return ds, init, lu


def _run_inproc(bcast, bcast_codec="", codec="none", rounds=4, seed=1):
    ds, init, lu = _problem(seed)
    bus = InprocBus()
    sb = bus.register(0)
    cbs = [bus.register(i + 1) for i in range(2)]
    server = FedAvgServerManager(
        sb, init, num_clients=2, clients_per_round=2, comm_rounds=rounds,
        seed=seed, codec=codec, stats_plane=False,
        bcast=bcast, bcast_codec=bcast_codec,
    )
    clients = [
        FedAvgClientManager(cb, lu, ds, batch_size=16,
                            template_variables=init, seed=seed)
        for cb in cbs
    ]
    server.start()
    bus.drain()
    assert server.round_idx == rounds
    leaves = [np.asarray(l).copy()
              for l in jax.tree_util.tree_leaves(server.variables)]
    return leaves, [c.upload_digest for c in clients]


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_delta_vs_full_same_chain_byte_identical(codec):
    """THE delta pin: ``--bcast delta`` is a pure WIRE change — at the
    same chain codec, a delta run and a full-broadcast run produce
    byte-identical upload digests and final models, for fp32 AND
    int8+EF uplinks."""
    delta = _run_inproc("delta", codec=codec)
    full = _run_inproc("full", bcast_codec="qsgd8", codec=codec)
    assert delta[1] == full[1], "upload digests differ"
    for a, b in zip(delta[0], full[0]):
        assert a.tobytes() == b.tobytes(), "final model differs"


def test_delta_rerun_deterministic():
    a = _run_inproc("delta")
    b = _run_inproc("delta")
    assert a[1] == b[1]
    for x, y in zip(a[0], b[0]):
        assert x.tobytes() == y.tobytes()


def test_delta_counts_bcast_bytes_and_shrinks_payload():
    """The int8 chain update is ~4x smaller than the fp32 model it
    replaces on the wire (per-chunk scales cost a little)."""
    before = _counters()
    _run_inproc("delta")
    after = _counters()
    model_bytes = (8 * 2 + 2) * 4
    sent = after.get("comm.delta_bcast_bytes", 0) \
        - before.get("comm.delta_bcast_bytes", 0)
    assert sent > 0
    # 3 delta syncs (rounds 1..3) x 2 groups at most; each update must
    # be well under the fp32 model it replaces
    assert sent < 3 * model_bytes


def test_chain_quantization_error_is_fed_back():
    """The downlink EF recurrence: each round's residual rides into the
    next encode, so the chain tracks the exact aggregate to within one
    quantization step instead of a random walk."""
    tree = {"w": np.zeros(512, np.float32)}
    target = {"w": np.linspace(-0.1, 0.1, 512).astype(np.float32)}
    model = tree
    resid = {"w": np.zeros(512, np.float32)}
    for r in range(6):
        raw = {"w": target["w"] - np.asarray(model["w"], np.float32)
               + resid["w"]}
        wire = encode_bcast_delta("qsgd8", raw, seed=0, round_idx=r)
        dec = tree_from_wire(wire, tree)
        resid = {"w": raw["w"] - np.asarray(dec["w"], np.float32)}
        model = apply_bcast_delta(model, dec)
    err = np.abs(model["w"] - target["w"]).max()
    assert err < 2e-3, f"chain drifted: {err}"


class _Capture(CommBackend):
    def __init__(self, node_id: int = 0):
        super().__init__(node_id)
        self.unicasts = []
        self.mcasts = []

    def send_message(self, msg):
        self.unicasts.append(msg)

    def send_multicast(self, msg, receivers):
        self.mcasts.append((msg, list(receivers)))

    def run(self):
        ...

    def stop(self):
        ...


def test_broadcast_delta_grouping_window_and_no_ack():
    """Grouping unit: acked-in-window nodes share a delta mcast per
    base round; a base older than the bounded delta log (stale-base
    eviction) and a node with no ack both force the counted full-model
    fallback."""
    _, init, _ = _problem()
    cap = _Capture()
    server = FedAvgServerManager(
        cap, init, num_clients=3, clients_per_round=3, comm_rounds=20,
        seed=1, stats_plane=False, bcast="delta", delta_base_window=2,
    )
    zeros = jax.tree_util.tree_map(
        lambda l: np.zeros_like(np.asarray(l, np.float32)), init)
    with server._ack_lock:
        server._delta_log[4] = encode_bcast_delta(
            "qsgd8", zeros, seed=1, round_idx=4)
        server._delta_log[5] = encode_bcast_delta(
            "qsgd8", zeros, seed=1, round_idx=5)
        server._acked.update({1: 4, 2: 2})  # node 3: no ack at all
    server.round_idx = 5
    before = _counters()
    server._broadcast_model(MSG_TYPE_S2C_SYNC_MODEL)
    after = _counters()
    deltas = [(m, r) for m, r in cap.mcasts
              if m.get(MSG_ARG_KEY_DELTA_BASE) is not None]
    fulls = [(m, r) for m, r in cap.mcasts
             if m.get(MSG_ARG_KEY_DELTA_BASE) is None]
    assert len(deltas) == 1
    msg, rcv = deltas[0]
    assert rcv == [1] and msg.get(MSG_ARG_KEY_DELTA_BASE) == 4
    assert len(msg.get(MSG_ARG_KEY_MODEL_PARAMS)) == 1  # delta for r=5
    assert len(fulls) == 1 and sorted(fulls[0][1]) == [2, 3]
    for reason in ("window", "no_ack"):
        key = f"comm.delta_full_fallbacks{{reason={reason}}}"
        assert after.get(key, 0) - before.get(key, 0) == 1, reason


def test_client_resync_on_unknown_base():
    """A delta against a base the client never saw: no training, one
    RESYNC upstream — and the server's handler clears the ack and
    unicasts the full current model."""
    _, init, lu = _problem()
    ds, _, _ = _problem()
    cap = _Capture(node_id=1)
    client = FedAvgClientManager(cap, lu, ds, batch_size=16,
                                 template_variables=init, seed=1)
    msg = Message(MSG_TYPE_S2C_SYNC_MODEL, 0, 1)
    msg.add_params(MSG_ARG_KEY_MODEL_PARAMS,
                   [encode_bcast_delta("qsgd8", init, seed=1, round_idx=3)])
    msg.add_params(MSG_ARG_KEY_DELTA_BASE, 2)
    msg.add_params(MSG_ARG_KEY_ROUND_INDEX, 3)
    msg.add_params("delta_window", 4)
    client._on_sync(msg)
    assert len(cap.unicasts) == 1
    assert cap.unicasts[0].type == MSG_TYPE_C2S_RESYNC
    assert cap.unicasts[0].get(MSG_ARG_KEY_ROUND_INDEX) == 3

    # server side: the resync clears the ack and resends full
    scap = _Capture()
    server = FedAvgServerManager(
        scap, init, num_clients=3, clients_per_round=3, comm_rounds=20,
        seed=1, stats_plane=False, bcast="delta",
    )
    with server._ack_lock:
        server._acked[1] = 2
    server.round_idx = 3
    server._on_resync(cap.unicasts[0].clone_for(0))
    with server._ack_lock:
        assert 1 not in server._acked
    assert len(scap.unicasts) == 1
    resent = scap.unicasts[0]
    assert resent.type == MSG_TYPE_S2C_SYNC_MODEL
    assert resent.get(MSG_ARG_KEY_DELTA_BASE) is None
    assert resent.get(MSG_ARG_KEY_ROUND_INDEX) == 3


def test_resync_recovery_preserves_chain_byte_identity():
    """Mid-run amnesia (the rejoin shape): wipe one client's base cache
    after a couple of rounds — the resync walkback must land it on the
    SAME chain, so the final model equals an uninterrupted delta run's,
    byte for byte."""
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    def run(amnesia: bool):
        ds, init, lu = _problem()
        hub = TcpHub()
        backends = []
        try:
            sb = TcpBackend(0, hub.host, hub.port)
            backends.append(sb)
            cbs = [TcpBackend(i + 1, hub.host, hub.port) for i in range(2)]
            backends += cbs
            server = FedAvgServerManager(
                sb, init, num_clients=2, clients_per_round=2,
                comm_rounds=5, seed=1, stats_plane=False, bcast="delta",
                round_timeout=30.0,
            )
            clients = [
                FedAvgClientManager(cb, lu, ds, batch_size=16,
                                    template_variables=init, seed=1)
                for cb in cbs
            ]
            threads = [cb.run_in_thread() for cb in cbs]
            st = sb.run_in_thread()
            server.start()
            if amnesia:
                deadline = time.monotonic() + 60
                while server.round_idx < 2 and time.monotonic() < deadline:
                    time.sleep(0.01)
                clients[1]._bases.clear()  # fresh-process simulation
            st.join(timeout=120)
            assert not st.is_alive()
            assert server.round_idx == 5
            for t in threads:
                t.join(timeout=15)
            return ([np.asarray(l).copy() for l in
                     jax.tree_util.tree_leaves(server.variables)],
                    [c.upload_digest for c in clients])
        finally:
            for b in backends:
                b.stop()
            hub.stop()

    # the wipe races the federation from the test thread: on a starved
    # box all 5 rounds can finish before clear() lands, so the run
    # triggers no resync — retry the setup (bounded), the identity
    # assertion itself is unconditional
    for _attempt in range(3):
        before = _counters()
        wiped = run(amnesia=True)
        after = _counters()
        if after.get("comm.delta_resyncs", 0) \
                > before.get("comm.delta_resyncs", 0):
            break
    assert after.get("comm.delta_resyncs", 0) \
        > before.get("comm.delta_resyncs", 0), "amnesia never triggered"
    clean = run(amnesia=False)
    for a, b in zip(wiped[0], clean[0]):
        assert a.tobytes() == b.tobytes(), "resync diverged the chain"


def _fed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def test_muxed_shm_delta_matches_per_process_full(tmp_path):
    """Composition pin across EVERY new lever at once: a muxed
    federation over the shm lane with delta broadcast equals a
    one-process-per-client pure-TCP full-broadcast federation at the
    same chain codec — upload digests and final model byte-identical."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    env = _fed_env()
    results = {}
    arms = {
        "mux_shm_delta": dict(muxers=1, lane="shm", shm_min_bytes=0,
                              bcast="delta"),
        "proc_tcp_full": dict(muxers=0, bcast="full",
                              bcast_codec="qsgd8"),
    }
    for tag, kw in arms.items():
        out = str(tmp_path / f"final_{tag}.npz")
        info = {}
        rc = launch(num_clients=3, rounds=2, seed=0, batch_size=16,
                    out_path=out, env=env, info=info, timeout=240.0,
                    **kw)
        assert rc == 0, f"{tag} federation failed"
        z = np.load(out)
        leaves = [np.asarray(z[k]) for k in sorted(z.files)
                  if k.startswith("leaf_")]
        digests = {k: v for k, v in sorted(info.items())
                   if k.endswith("_upload_digest")}
        results[tag] = (leaves, digests)
    a, b = results["mux_shm_delta"], results["proc_tcp_full"]
    assert a[1] == b[1], "upload digests differ across topologies"
    for x, y in zip(a[0], b[0]):
        np.testing.assert_array_equal(x, y)


@pytest.mark.slow
def test_connection_churn_soak_rejoin_every_round(tmp_path):
    """PR 10's leftover, over the new transport: muxers drop +
    re-hello every round with amnesia — rebind counters grow, the delta
    broadcast walks every rejoiner through the full-model path, and the
    federation still finishes finite."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / "final_churn.npz")
    info = {}
    rc = launch(num_clients=6, rounds=5, seed=0, batch_size=16,
                out_path=out, muxers=2, bcast="delta", lane="shm",
                shm_min_bytes=0, mux_rejoin_every_round=True,
                auto_reconnect=1000, round_timeout=15.0,
                env=_fed_env(), info=info, timeout=400.0)
    assert rc == 0
    z = np.load(out)
    assert all(np.isfinite(np.asarray(z[k])).all()
               for k in z.files if k.startswith("leaf_"))
    hub_stats = info.get("hub_stats") or {}
    assert hub_stats.get("node_rebinds", 0) >= 2 * 3, hub_stats
    faults = info.get("faults") or {}
    fallbacks = sum(v for k, v in faults.items()
                    if k.startswith("comm.delta_full_fallbacks"))
    assert fallbacks > 0, faults
