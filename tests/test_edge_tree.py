"""Hierarchical edge-hub aggregation (PR 17): two-tier topology where
edge hubs terminate their cohort's connections, partially fold uploads
with the same O(1) streaming aggregation the server runs, and forward
ONE ``(sum n*model, sum n)`` pair upstream per round.

The in-process tests pin the algebra the topology relies on: fp64
num/den partials COMPOSE EXACTLY, so folding per-edge partials at the
root is bit-equal to folding every upload flat.  The federation tests
spawn the true multi-process tree (``--role edge_hub``) and hold the
tentpole acceptance bar — same seed, same codec, tree vs flat: upload
digests equal byte for byte and the final global models bit-equal —
across fp32/int8+EF, muxed/per-process, and the full downlink
composition (striped fanout + delta broadcast + shm lanes) crossing
the extra hop.
"""

import json
import os

import numpy as np
import pytest

from fedml_tpu.core import tree as treelib


def _fed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def _digests(info):
    return {k: v for k, v in sorted(info.items())
            if k.endswith("_upload_digest")}


def _leaves(out_path):
    z = np.load(out_path)
    return [np.asarray(z[k]) for k in sorted(z.files)
            if k.startswith("leaf_")]


# --- in-process: the partial-fold algebra ------------------------------------

def _rand_tree(rng):
    return {
        "w": rng.standard_normal((5, 3)).astype(np.float32),
        "b": rng.standard_normal((3,)).astype(np.float32),
    }


def test_tiered_fold_composes_bitwise():
    """Edge hubs fold their cohort into fp64 (num, den) partials; the
    root folds the PARTIALS.  Exactness of the composition is what
    makes the tree topology-invisible: fold(fold(A), fold(B)) must be
    bit-equal to fold(A + B) in one flat pass, for any contiguous
    partition of the cohort."""
    rng = np.random.default_rng(17)
    uploads = [(_rand_tree(rng), float(w))
               for w in rng.integers(1, 90, size=12)]

    def fold(pairs):
        acc, total = None, 0.0
        for t, w in pairs:
            acc = treelib.tree_fold_weighted(acc, t, w)
            total += w
        return acc, total

    flat_acc, flat_n = fold(uploads)
    for split in (1, 4, 7, 11):
        # tier 1: per-edge partials; tier 2: root folds partials with
        # weight 1 (the num is already n-weighted, the den rides along)
        root_acc, root_n = None, 0.0
        for g in (uploads[:split], uploads[split:]):
            part_acc, part_n = fold(g)
            root_acc = treelib.tree_fold_weighted(root_acc, part_acc, 1.0)
            root_n += part_n
        assert root_n == flat_n
        for a, b in zip(_flat(root_acc), _flat(flat_acc)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        flat_mean = treelib.tree_finalize_weighted_mean(
            flat_acc, flat_n, uploads[0][0])
        tree_mean = treelib.tree_finalize_weighted_mean(
            root_acc, root_n, uploads[0][0])
        for a, b in zip(_flat(tree_mean), _flat(flat_mean)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _flat(t):
    import jax

    return jax.tree_util.tree_flatten(t)[0]


# --- federation: tree vs flat byte-identity ----------------------------------

def _run(tmp_path, tag, **kw):
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / f"final_{tag}.npz")
    info = {}
    rc = launch(seed=0, batch_size=16, out_path=out,
                env=_fed_env(), info=info, timeout=300.0, **kw)
    assert rc == 0, f"{tag} federation failed (rc={rc})"
    return _digests(info), _leaves(out), info


def _assert_tree_matches_flat(tmp_path, codec, muxers):
    # muxers=2 (not 1): a muxer owns its whole virtual range and is
    # indivisible under the tree partition — one muxer for the full
    # cohort would collapse the tree to a single edge
    base = dict(num_clients=6, rounds=2, codec=codec, muxers=muxers)
    dig_flat, leaves_flat, _ = _run(tmp_path, f"flat_{codec}", **base)
    dig_tree, leaves_tree, info = _run(
        tmp_path, f"tree_{codec}", topology="tree", edge_hubs=2, **base)
    assert len(dig_flat) == 6 and dig_flat == dig_tree
    for a, b in zip(leaves_flat, leaves_tree):
        np.testing.assert_array_equal(a, b)
    stats = [v for k, v in info.items() if k.endswith("_stats")
             and k.startswith("edge_")]
    assert len(stats) == 2
    for s in stats:
        assert s["folded_uploads"] > 0
        assert s["flat_fallbacks"] == 0


@pytest.mark.parametrize("codec,muxers", [("none", 0), ("int8", 2)])
def test_tree_vs_flat_byte_identical(tmp_path, codec, muxers):
    """THE tentpole pin: same seed, same codec — a two-edge tree
    federation's per-client upload digests equal the flat federation's
    byte for byte, and the final global models are bit-equal.  Covers
    fp32 per-process clients and int8+EF muxed virtual clients (the
    slow-marked cross pairs complete the matrix)."""
    _assert_tree_matches_flat(tmp_path, codec, muxers)


@pytest.mark.slow
@pytest.mark.parametrize("codec,muxers", [("none", 2), ("int8", 0)])
def test_tree_vs_flat_byte_identical_cross(tmp_path, codec, muxers):
    """The other half of the codec x process-shape matrix."""
    _assert_tree_matches_flat(tmp_path, codec, muxers)


def test_tree_downlink_composition_byte_identical(tmp_path):
    """The downlink stack crosses the extra hop once per EDGE link and
    the edge re-fans out: striped fanout + delta-chain broadcast + shm
    lanes + int8 uploads on one muxed tree federation must still match
    the flat run bit-for-bit.  The tree side runs with inline decodes
    (decode_workers=0) against the flat side's pooled decodes, so
    byte-equality also pins decode-pool invariance across topologies."""
    base = dict(num_clients=6, rounds=3, codec="int8", muxers=2,
                lane="shm", bcast="delta", fanout="striped")
    dig_flat, leaves_flat, _ = _run(
        tmp_path, "flat_comp", decode_workers=2, **base)
    dig_tree, leaves_tree, _ = _run(
        tmp_path, "tree_comp", topology="tree", edge_hubs=2,
        decode_workers=0, **base)
    assert len(dig_flat) == 6 and dig_flat == dig_tree
    for a, b in zip(leaves_flat, leaves_tree):
        np.testing.assert_array_equal(a, b)


def test_tree_smoke_64_virtual_clients(tmp_path):
    """Tier-1 smoke at the scale shape FEDTREE_r17 extrapolates from:
    64 virtual clients on two muxers behind two edge hubs — the root
    sees 2 aggregation connections instead of 64.  Every round
    aggregates the full cohort, leaves stay finite, and both edges
    report clean folds (no flat fallbacks)."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    out = str(tmp_path / "final_tree64.npz")
    info = {}
    rc = launch(num_clients=64, rounds=2, seed=0, batch_size=16,
                out_path=out, muxers=2, topology="tree", edge_hubs=2,
                env=_fed_env(), info=info, timeout=300.0)
    assert rc == 0
    z = np.load(out)
    assert int(z["rounds"]) == 2
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert all(r["participants"] == list(range(1, 65)) for r in rounds)
    for k in z.files:
        if k.startswith("leaf_"):
            assert np.isfinite(z[k]).all()
    stats = [v for k, v in info.items() if k.startswith("edge_")
             and k.endswith("_stats")]
    assert len(stats) == 2
    for s in stats:
        assert s["folded_uploads"] > 0
        assert s["flat_fallbacks"] == 0
        # the whole cohort's uploads left the edge as O(groups) partial
        # frames, not O(clients) — the point of the tier
        assert s["uplink_frames"] <= 2 * 2 + 2  # rounds * groups + slack


# --- range-claim hellos: O(edges) root state ---------------------------------

def _wait(cond, timeout=15.0):
    import time

    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert cond(), "condition never held"


class _Collect:
    def __init__(self, sink, key):
        self.sink, self.key = sink, key

    def receive_message(self, t, m):
        self.sink.setdefault(self.key, []).append(m)


def test_range_hello_keeps_root_state_o_edges():
    """A contiguous edge cohort registers as ONE ``[lo, hi]`` range
    claim: the root hub's per-id map stays empty for the cohort (its
    routing state is O(edges), the fix for the measured +33 MB
    registration tax at 100k per-id claims) while the ``nodes`` gauge
    still counts every virtual client — and the peers barrier is
    satisfied through the range, so coordinators need no change."""
    from fedml_tpu.comm.edge import EdgeUplinkBackend
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    hub = TcpHub()
    edge = sender = None
    try:
        cohort = list(range(10, 210))  # 200 contiguous ids
        edge = EdgeUplinkBackend(cohort, hub.host, hub.port)
        assert edge._hello_obj() == {"node_ranges": [[10, 209]]}
        edge.run_in_thread()
        sender = TcpBackend(500, hub.host, hub.port)
        # the barrier resolves the cohort against the [lo, hi] claim
        sender.await_peers(cohort + [500], timeout=15.0)
        stats = hub.stats()
        assert stats["nodes"] == 201  # 200 claimed by range + sender
        assert stats["connections"] == 2
        assert stats["range_conns"] == 1
        with hub._lock:
            assert not any(n in hub._conns for n in cohort)
    finally:
        for b in (edge, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_range_mcast_compacts_meta_and_expands_at_edge():
    """A broadcast covering the WHOLE cohort ships one wrapped copy
    whose meta is the two-int ``range`` (never a 100k-id list — the
    689 KB sync-frame tax); the edge expands it locally so the re-fan
    target list is unchanged.  A partial broadcast falls back to the
    explicit ``nodes`` list."""
    import numpy as np

    from fedml_tpu.comm.edge import EdgeUplinkBackend
    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    frames = []

    class _Spy(EdgeUplinkBackend):
        def _on_mux_frame(self, frame, payload, nbytes, region=None):
            frames.append(dict(frame))
            super()._on_mux_frame(frame, payload, nbytes, region=region)

    hub = TcpHub()
    got = {}
    edge = sender = None
    try:
        cohort = list(range(1, 9))
        edge = _Spy(cohort, hub.host, hub.port)
        edge.add_observer(_Collect(got, "edge"))
        edge.run_in_thread()
        sender = TcpBackend(99, hub.host, hub.port)
        sender.await_peers(cohort, timeout=15.0)
        m = Message("SYNC", 99, -1)
        m.add_params("model", np.arange(8, dtype=np.float32))
        sender.send_multicast(m, cohort)
        _wait(lambda: len(got.get("edge", ())) >= 1)
        assert frames[0].get("range") == [1, 8]
        assert frames[0].get("nodes") is None
        assert getattr(got["edge"][0], "_mux_nodes", None) == cohort
        # partial cohort: explicit list, no range compaction
        sender.send_multicast(m, cohort[:3])
        _wait(lambda: len(got.get("edge", ())) >= 2)
        assert frames[1].get("range") is None
        assert frames[1].get("nodes") == cohort[:3]
        assert getattr(got["edge"][1], "_mux_nodes", None) == cohort[:3]
    finally:
        for b in (edge, sender):
            if b is not None:
                b.stop()
        hub.stop()


def test_range_claim_displaced_as_one_atom():
    """Ranges are rebind ATOMS: a later hello overlapping ANY id in a
    range claim displaces the whole connection (counted as one rebind
    per covered id), never a partial carve-out — partial range
    mutation would reintroduce per-id bookkeeping at the root."""
    from fedml_tpu.comm.edge import EdgeUplinkBackend
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    hub = TcpHub()
    edge = thief = None
    try:
        edge = EdgeUplinkBackend(list(range(1, 9)), hub.host, hub.port)
        edge.run_in_thread()
        _wait(lambda: hub.stats()["range_conns"] == 1)
        thief = TcpBackend(4, hub.host, hub.port)  # overlaps the claim
        thief.run_in_thread()
        _wait(lambda: hub.stats()["node_rebinds"] >= 8)
        stats = hub.stats()
        assert stats["range_conns"] == 0
        assert stats["node_rebinds"] == 8  # all 8 covered ids, at once
        assert stats["nodes"] == 1  # only the thief remains
    finally:
        for b in (edge, thief):
            if b is not None:
                b.stop()
        hub.stop()


def test_noncontiguous_cohort_falls_back_to_per_id_hello():
    """A gap in the cohort disables range compaction: the hello lists
    ids (hello v2) and the hub registers per-id — correctness never
    depends on the launcher's contiguous partitioning."""
    from fedml_tpu.comm.edge import EdgeUplinkBackend
    from fedml_tpu.comm.tcp import TcpHub

    hub = TcpHub()
    edge = None
    try:
        cohort = [1, 2, 3, 5]  # hole at 4
        edge = EdgeUplinkBackend(cohort, hub.host, hub.port)
        assert edge._hello_obj() == {"node_ids": cohort}
        edge.run_in_thread()
        _wait(lambda: hub.stats()["nodes"] == 4)
        stats = hub.stats()
        assert stats["range_conns"] == 0
        assert stats["connections"] == 1
        with hub._lock:
            assert all(n in hub._conns for n in cohort)
    finally:
        if edge is not None:
            edge.stop()
        hub.stop()
