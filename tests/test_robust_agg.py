"""Robust + private aggregation (fedml_tpu/robust + core/robust):

- np-vs-jnp parity of the ONE shared defense-math implementation
  (sim transform and server hot path cannot drift);
- streaming screening: clip semantics, outlier-reject counted-never-
  silent, honest uploads untouched (byte-identity with undefended);
- buffered median / trimmed-mean leaf-exact vs an independent numpy
  oracle;
- per-connection contribution caps (water-filling math + a dominant
  muxer connection through the server close);
- client-level DP noise bit-reproducible from the fold_in stream;
- arrival-order independence of the defended close;
- Byzantine FaultRule attacks (sign_flip / scale_grad) through the
  chaos layer;
- the SLO engine's max_outlier_uploads budget;
- muxed-vs-per-process defended federations producing identical
  models (real OS processes).
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg_cross_device import FedAvgServerManager
from fedml_tpu.comm.inproc import InprocBus
from fedml_tpu.comm.message import (
    MSG_ARG_KEY_MODEL_PARAMS,
    MSG_ARG_KEY_NUM_SAMPLES,
    MSG_ARG_KEY_ROUND_INDEX,
    MSG_TYPE_C2S_SEND_MODEL,
    Message,
    tree_from_wire,
    tree_to_wire,
)
from fedml_tpu.core import robust as robustlib
from fedml_tpu.core import tree as treelib
from fedml_tpu.faults import (
    ChaosBackend,
    FaultPlan,
    FaultRule,
    attack_message,
)
from fedml_tpu.obs.telemetry import get_telemetry
from fedml_tpu.robust import (
    DefenseConfig,
    RobustAggregator,
    cap_connection_weights,
)

RNG = np.random.RandomState(42)


def _params(shape_seed=0):
    rng = np.random.RandomState(shape_seed)
    return {"w": rng.randn(4, 3).astype(np.float32),
            "b": rng.randn(3).astype(np.float32)}


def _stacked(k, scale=1.0, seed=1):
    rng = np.random.RandomState(seed)
    return {"w": (rng.randn(k, 4, 3) * scale).astype(np.float32),
            "b": (rng.randn(k, 3) * scale).astype(np.float32)}


# ---------------------------------------------------------------------------
# one implementation: np == jnp


def test_defense_math_np_jnp_parity():
    gp, sp = _params(), _stacked(5, scale=3.0)
    for fn in (
        lambda xp: robustlib.param_delta_norms(gp, sp, xp=xp),
        lambda xp: robustlib.clip_stacked_params(gp, sp, 1.0, xp=xp),
        lambda xp: robustlib.coordinate_median(sp, xp=xp),
        lambda xp: robustlib.trimmed_mean(sp, 0.2, xp=xp),
    ):
        a = jax.tree_util.tree_leaves(fn(np))
        b = jax.tree_util.tree_leaves(fn(jnp))
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-7)


def test_screen_clip_matches_sim_transform_row():
    """The server's per-upload (K=1, numpy) clip equals the compiled
    transform's row for the same client — the sim-vs-cross-device
    parity pin the dedup satellite asks for."""
    gvars = {"params": _params()}
    sp = _stacked(3, scale=2.0)
    transform = robustlib.make_robust_transform(
        "norm_diff_clipping", norm_bound=0.7)
    stacked_out = transform(gvars, {"params": sp}, None, None)
    ra = RobustAggregator(
        DefenseConfig(defense="streaming", norm_bound=0.7), seed=0)
    for k in range(3):
        row = {"params": jax.tree_util.tree_map(lambda s, k=k: s[k], sp)}
        out, _ = ra.screen(row, gvars, round_idx=0, slot=k)
        for a, b in zip(
            jax.tree_util.tree_leaves(out["params"]),
            [np.asarray(l)[k]
             for l in jax.tree_util.tree_leaves(stacked_out["params"])],
        ):
            np.testing.assert_allclose(np.asarray(a), b,
                                       rtol=1e-6, atol=1e-7)


def test_weak_dp_noise_key_parity_with_engine_stream():
    """Server-side DP noise uses the engine's exact aggregation-noise
    key chain — fold_in(fold_in(fold_in(seed_key, round), AGG_STREAM),
    slot) — so for the same (seed, round, slot) the noise is the
    engine's weak-DP noise bit-for-bit."""
    gp = _params()
    key_engine = jax.random.fold_in(
        jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(7), 3),
            robustlib.AGG_STREAM,
        ),
        11,
    )
    a = robustlib.noise_params(key_engine, gp, 0.05)
    b = robustlib.noise_params(
        robustlib.agg_noise_key(jax.random.PRNGKey(7), 3, 11), gp, 0.05)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_dp_noise_reproducible_and_slot_independent():
    cfg = DefenseConfig(defense="streaming", norm_bound=10.0,
                        dp_clip=5.0, dp_noise=0.1)
    base = {"params": _params()}
    up = {"params": jax.tree_util.tree_map(lambda g: g + 0.1,
                                           base["params"])}
    outs = [RobustAggregator(cfg, seed=3).screen(
        dict(up), base, round_idx=2, slot=4)[0] for _ in range(2)]
    for x, y in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    other_slot, _ = RobustAggregator(cfg, seed=3).screen(
        dict(up), base, round_idx=2, slot=5)
    assert any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(outs[0]),
                        jax.tree_util.tree_leaves(other_slot))
    )


def test_defense_config_validation():
    with pytest.raises(ValueError):
        DefenseConfig(defense="nope")
    with pytest.raises(ValueError):
        DefenseConfig(defense="streaming", outlier_mult=2.0)  # no bound
    with pytest.raises(ValueError):
        DefenseConfig(defense="median", conn_cap=0.4)  # caps = streaming
    with pytest.raises(ValueError):
        DefenseConfig(defense="streaming", conn_cap=1.5)
    with pytest.raises(ValueError):
        DefenseConfig(dp_noise=0.1)  # noise without a clip bound
    with pytest.raises(ValueError):
        # a bound without its mode would be silently inert
        DefenseConfig(norm_bound=1.0)
    assert not DefenseConfig().enabled
    assert DefenseConfig(defense="median").buffered


def test_conn_cap_refused_on_legacy_hotpath():
    """conn_cap is enforced by the streaming fold's per-conn
    accumulators — on the legacy buffered path it would be silently
    unenforced, so the manager refuses the combination outright."""
    bus = InprocBus()
    backend = bus.register(0)
    init = {"params": {"w": np.zeros((2, 2), np.float32)}}
    with pytest.raises(ValueError):
        FedAvgServerManager(
            backend, init, num_clients=2, clients_per_round=2,
            comm_rounds=1, seed=0, streaming_agg=False, stats_plane=False,
            defense=DefenseConfig(defense="streaming", norm_bound=1.0,
                                  conn_cap=0.5),
        )


def test_dp_clip_only_counts_as_clipped():
    """A clip triggered by dp_clip (no streaming norm bound) must still
    count — a mutation with zero telemetry violates the
    counted-never-silent discipline."""
    cfg = DefenseConfig(dp_clip=0.2)
    ra = RobustAggregator(cfg, seed=0)
    base = {"params": _params()}
    up = {"params": jax.tree_util.tree_map(lambda g: g + 1.0,
                                           base["params"])}
    out, flags = ra.screen(up, base, round_idx=0, slot=0)
    assert flags["clipped"] is True
    norm = float(robustlib.param_delta_norms(
        jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                               base["params"]),
        {k: np.asarray(v)[None] for k, v in out["params"].items()},
        xp=np)[0])
    assert norm == pytest.approx(0.2, rel=1e-5)


# ---------------------------------------------------------------------------
# connection caps


def test_cap_connection_weights_math():
    # dominant conn capped to exactly the cap fraction of the new total
    scales, inf = cap_connection_weights({"a": 80.0, "b": 10.0, "c": 10.0},
                                         0.4)
    assert not inf
    w = {"a": 80.0, "b": 10.0, "c": 10.0}
    total = sum(scales[k] * w[k] for k in w)
    assert scales["b"] == scales["c"] == 1.0
    assert scales["a"] * w["a"] / total == pytest.approx(0.4)
    # two conns over the cap: both land exactly at cap
    w2 = {"a": 50.0, "b": 30.0, "c": 20.0}
    scales2, inf2 = cap_connection_weights(w2, 0.34)
    assert not inf2
    t2 = sum(scales2[k] * w2[k] for k in w2)
    assert scales2["a"] * 50.0 / t2 == pytest.approx(0.34)
    assert scales2["b"] * 30.0 / t2 == pytest.approx(0.34)
    assert scales2["c"] == 1.0
    # infeasible: equal weights under the cap — loudly unapplied
    scales3, inf3 = cap_connection_weights({"a": 10.0, "b": 10.0}, 0.4)
    assert inf3 and all(v == 1.0 for v in scales3.values())
    # single conn carrying the whole round: its fraction is 1 > cap
    # by definition — infeasible, loudly (never silently uncapped)
    assert cap_connection_weights({"a": 5.0}, 0.4) == ({"a": 1.0}, True)


def _mk_server(defense, *, num_clients=4, clients_per_round=4, spares=0,
               comm_rounds=1, init=None):
    bus = InprocBus()
    backend = bus.register(0)
    for i in range(1, num_clients + 1):
        bus.register(i)
    init = init if init is not None else {
        "params": {"w": np.zeros((4, 3), np.float32),
                   "b": np.zeros((3,), np.float32)}}
    server = FedAvgServerManager(
        backend, init, num_clients=num_clients,
        clients_per_round=clients_per_round, comm_rounds=comm_rounds,
        seed=0, spares=spares, stats_plane=False, defense=defense,
    )
    return server


def _upload(server, sender, tree, n, round_idx=0):
    m = Message(MSG_TYPE_C2S_SEND_MODEL, sender, 0)
    m.add_params(MSG_ARG_KEY_ROUND_INDEX, round_idx)
    m.add_params(MSG_ARG_KEY_MODEL_PARAMS, tree_to_wire(tree))
    m.add_params(MSG_ARG_KEY_NUM_SAMPLES, float(n))
    server._on_model(m)


def test_conn_cap_dominant_muxer_through_close():
    """Clients 1-3 share one connection (a muxer) with a dominant
    weight share; client 4 dials alone.  The close must rescale the
    muxed connection to exactly the cap fraction — oracle recomputed
    from the raw uploads + the cap math."""
    cfg = DefenseConfig(defense="streaming", conn_cap=0.5)
    server = _mk_server(cfg)
    server._robust.set_conn_map({1: [1, 2, 3], 2: [4]})
    trees = [{"params": {"w": np.full((4, 3), float(i + 1), np.float32),
                         "b": np.full((3,), float(i + 1), np.float32)}}
             for i in range(4)]
    ns = [30.0, 30.0, 30.0, 10.0]  # conn1 = 90 vs conn2 = 10
    for i, (t, n) in enumerate(zip(trees, ns)):
        _upload(server, i + 1, t, n)
    assert server.round_idx == 1
    # oracle: per-conn num/den, conn1 rescaled so its share == cap
    scales, inf = cap_connection_weights({"conn1": 90.0, "conn2": 10.0},
                                         0.5)
    assert not inf and scales["conn1"] < 1.0
    # direct oracle: scaled fp64 num/den
    num64 = None
    den = 0.0
    for conn, idxs in (("conn1", (0, 1, 2)), ("conn2", (3,))):
        cacc = None
        cn = 0.0
        for i in idxs:
            cacc = treelib.tree_fold_weighted(cacc, trees[i], ns[i])
            cn += ns[i]
        scaled = treelib.tree_scale(cacc, scales[conn])
        num64 = scaled if num64 is None else treelib.tree_add(num64, scaled)
        den += scales[conn] * cn
    expected = treelib.tree_finalize_weighted_mean(
        num64, den, trees[0])
    for a, b in zip(jax.tree_util.tree_leaves(server.variables),
                    jax.tree_util.tree_leaves(expected)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rec = server.round_log[-1]
    assert rec["defense"]["capped_conns"] == 1


def test_conn_cap_infeasible_is_loud_noop():
    cfg = DefenseConfig(defense="streaming", conn_cap=0.3)
    server = _mk_server(cfg, num_clients=2, clients_per_round=2)
    server._robust.set_conn_map({1: [1], 2: [2]})
    t = get_telemetry()
    before = t.counter_value("robust.cap_infeasible")
    trees = [{"params": {"w": np.ones((4, 3), np.float32),
                         "b": np.ones((3,), np.float32)}}] * 2
    for i in range(2):
        _upload(server, i + 1, trees[i], 10.0)
    assert server.round_idx == 1
    assert server.round_log[-1]["defense"].get("cap_infeasible") is True
    assert t.counter_value("robust.cap_infeasible") == before + 1
    # weights left unscaled: plain mean
    for a in jax.tree_util.tree_leaves(server.variables):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.ones_like(np.asarray(a)))


# ---------------------------------------------------------------------------
# outlier reject / buffered estimators through the server


def test_outlier_reject_counted_never_silent():
    cfg = DefenseConfig(defense="streaming", norm_bound=1.0,
                        outlier_mult=3.0)
    server = _mk_server(cfg, num_clients=3, clients_per_round=2, spares=1)
    t = get_telemetry()
    before = t.counter_value("faults.observed", kind="outlier_upload",
                             msg_type=MSG_TYPE_C2S_SEND_MODEL)
    huge = {"params": {"w": np.full((4, 3), 50.0, np.float32),
                       "b": np.zeros((3,), np.float32)}}
    _upload(server, 1, huge, 5.0)
    assert server.round_idx == 0 and not server.pending
    assert server.rejected_uploads == 1
    assert t.counter_value("faults.observed", kind="outlier_upload",
                           msg_type=MSG_TYPE_C2S_SEND_MODEL) == before + 1
    assert any(e.get("kind") == "outlier_upload"
               for e in server.round_log if "rejected_from" in e)
    # the honest cohort still closes the round (K=2 of 3 with a spare)
    ok = {"params": {"w": np.full((4, 3), 0.01, np.float32),
                     "b": np.zeros((3,), np.float32)}}
    _upload(server, 2, ok, 5.0)
    _upload(server, 3, ok, 5.0)
    assert server.round_idx == 1
    assert server.round_log[-1]["defense"]["outliers"] == 1


@pytest.mark.parametrize("defense,trim", [("median", 0.2),
                                          ("trimmed_mean", 0.25)])
def test_buffered_estimators_leaf_exact_vs_numpy_oracle(defense, trim):
    cfg = DefenseConfig(defense=defense, trim_frac=trim)
    server = _mk_server(cfg, num_clients=5, clients_per_round=5)
    rng = np.random.RandomState(9)
    trees = [{"params": {"w": rng.randn(4, 3).astype(np.float32),
                         "b": rng.randn(3).astype(np.float32)}}
             for _ in range(5)]
    ns = [1.0, 2.0, 3.0, 4.0, 5.0]
    for i, (t, n) in enumerate(zip(trees, ns)):
        _upload(server, i + 1, t, n)
    assert server.round_idx == 1
    stack = {k: np.stack([t["params"][k] for t in trees])
             for k in ("w", "b")}
    if defense == "median":
        oracle = {k: np.median(stack[k].astype(np.float32), axis=0)
                  for k in stack}
    else:
        cut = int(trim * 5)
        srt = {k: np.sort(stack[k].astype(np.float32), axis=0)
               for k in stack}
        oracle = {k: np.mean(srt[k][cut:5 - cut], axis=0) for k in stack}
    for k in ("w", "b"):
        np.testing.assert_array_equal(
            np.asarray(server.variables["params"][k]),
            oracle[k].astype(np.float32),
        )
    # a Byzantine minority cannot move the median past honest values:
    # re-run with two wildly hostile uploads among five
    server2 = _mk_server(DefenseConfig(defense="median"),
                         num_clients=5, clients_per_round=5)
    hostile = [{"params": {"w": np.full((4, 3), s, np.float32),
                           "b": np.full((3,), s, np.float32)}}
               for s in (1e4, -1e4)]
    honest = trees[:3]
    for i, t in enumerate(honest + hostile):
        _upload(server2, i + 1, t, 1.0)
    med = np.asarray(server2.variables["params"]["w"])
    lo = np.min(np.stack([t["params"]["w"] for t in honest]), axis=0)
    hi = np.max(np.stack([t["params"]["w"] for t in honest]), axis=0)
    assert (med >= lo).all() and (med <= hi).all()


def test_streaming_defense_arrival_order_independent():
    """Same uploads, two arrival orders, defended streaming close →
    byte-identical models (per-upload screening is a pure function of
    (upload, base, seed, round, slot); the fp64 fold is exact at these
    magnitudes)."""
    rng = np.random.RandomState(5)
    trees = [{"params": {"w": rng.randn(4, 3).astype(np.float32) * s,
                         "b": rng.randn(3).astype(np.float32) * s}}
             for s in (0.1, 2.0, 0.3, 5.0)]
    ns = [3.0, 7.0, 11.0, 2.0]

    def run(order):
        cfg = DefenseConfig(defense="streaming", norm_bound=0.5,
                            dp_clip=0.4, dp_noise=0.02)
        server = _mk_server(cfg)
        for i in order:
            _upload(server, i + 1, trees[i], ns[i])
        assert server.round_idx == 1
        return server.variables

    a = run([0, 1, 2, 3])
    b = run([3, 1, 0, 2])
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_honest_uploads_bitwise_untouched_by_streaming_defense():
    """Defended and undefended rounds stay digest-comparable: uploads
    inside every bound take the EXACT undefended code path (no fp32
    rewrite), so an honest defended run is byte-identical to the
    undefended one."""
    rng = np.random.RandomState(6)
    trees = [{"params": {"w": rng.randn(4, 3).astype(np.float32) * 0.1,
                         "b": rng.randn(3).astype(np.float32) * 0.1}}
             for _ in range(4)]
    ns = [3.0, 7.0, 11.0, 2.0]

    def run(defense):
        server = _mk_server(defense)
        for i in range(4):
            _upload(server, i + 1, trees[i], ns[i])
        assert server.round_idx == 1
        return server.variables

    a = run(None)
    b = run(DefenseConfig(defense="streaming", norm_bound=100.0,
                          outlier_mult=10.0))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Byzantine FaultRules through the chaos layer


def test_attack_rule_plan_roundtrip():
    plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="scale_grad", node=3,
                         msg_type="C2S_SEND_MODEL", attack_scale=-10.0),
               FaultRule(action="sign_flip", node=4,
                         msg_type="C2S_SEND_MODEL")],
    )
    back = FaultPlan.from_json(plan.to_json())
    assert back.rules[0].attack_scale == -10.0
    assert back.rules[1].action == "sign_flip"
    acts = back.decide(3, "send", "C2S_SEND_MODEL", 0)
    assert acts and acts[0]["action"] == "scale_grad"
    assert acts[0]["attack_scale"] == -10.0
    with pytest.raises(ValueError):
        FaultRule(action="sign_flip", direction="stripe")


def test_attack_message_scales_every_float_leaf():
    tree = {"params": {"w": np.ones((2, 2), np.float32),
                       "steps": np.array([3], np.int32)}}
    m = Message(MSG_TYPE_C2S_SEND_MODEL, 1, 0)
    m.add_params(MSG_ARG_KEY_MODEL_PARAMS, tree_to_wire(tree))
    twin = attack_message(m, -1.0)
    assert twin is not None and twin is not m
    back = tree_from_wire(twin.get(MSG_ARG_KEY_MODEL_PARAMS), tree)
    np.testing.assert_array_equal(np.asarray(back["params"]["w"]),
                                  -np.ones((2, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(back["params"]["steps"]),
                                  [3])  # int leaves untouched
    # the original message payload is untouched (copy-on-write)
    orig = tree_from_wire(m.get(MSG_ARG_KEY_MODEL_PARAMS), tree)
    np.testing.assert_array_equal(np.asarray(orig["params"]["w"]),
                                  np.ones((2, 2), np.float32))


def test_chaos_sign_flip_and_scale_through_inproc():
    bus = InprocBus()
    plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="scale_grad", node=1,
                         msg_type="C2S_SEND_MODEL", direction="send",
                         attack_scale=10.0)],
    )
    sender = ChaosBackend(bus.register(1), plan)
    receiver = bus.register(0)
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append(m)

    receiver.add_observer(Obs())
    tree = {"params": {"w": np.full((2, 2), 2.0, np.float32)}}
    t = get_telemetry()
    before = t.counter_value("faults.injected", action="scale_grad",
                             msg_type=MSG_TYPE_C2S_SEND_MODEL)
    m = Message(MSG_TYPE_C2S_SEND_MODEL, 1, 0)
    m.add_params(MSG_ARG_KEY_MODEL_PARAMS, tree_to_wire(tree))
    sender.send_message(m)
    bus.drain()
    assert len(got) == 1
    back = tree_from_wire(got[0].get(MSG_ARG_KEY_MODEL_PARAMS), tree)
    np.testing.assert_array_equal(np.asarray(back["params"]["w"]),
                                  np.full((2, 2), 20.0, np.float32))
    assert t.counter_value("faults.injected", action="scale_grad",
                           msg_type=MSG_TYPE_C2S_SEND_MODEL) == before + 1


def test_attack_message_reaches_codec_payloads():
    """A sign-flip on a codec-encoded DELTA upload flips the decoded
    update (the stealth attack shape: honest norm, hostile direction)."""
    from fedml_tpu.compress import get_codec

    codec = get_codec("int8")
    tree = {"w": np.linspace(-1, 1, 16, dtype=np.float32).reshape(4, 4)}
    key = jax.random.PRNGKey(0)
    wire = tree_to_wire(tree, codec=codec, key=key, delta=True)
    m = Message(MSG_TYPE_C2S_SEND_MODEL, 1, 0)
    m.add_params(MSG_ARG_KEY_MODEL_PARAMS, wire)
    twin = attack_message(m, -1.0)
    assert twin is not None
    dec = tree_from_wire(twin.get(MSG_ARG_KEY_MODEL_PARAMS), tree)
    ref = tree_from_wire(wire, tree)
    np.testing.assert_allclose(np.asarray(dec["w"]),
                               -np.asarray(ref["w"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# SLO budget


def test_slo_max_outlier_uploads_budget():
    from fedml_tpu.obs.slo import SloEngine, SloSpec

    spec = SloSpec.from_obj({"max_outlier_uploads": 2})
    engine = SloEngine(spec)
    digest = {"counters": {
        "faults.observed{kind=outlier_upload,msg_type=C2S_SEND_MODEL}": 5
    }, "hists": {}}
    found = engine.evaluate(0, digest, {}, expected_nodes=None)
    assert any(v["objective"] == "outlier_uploads" and v["observed"] == 5
               for v in found)
    report = engine.report(digest, {})
    assert report["observed"]["outlier_uploads"] == 5
    assert not report["ok"]
    # inside budget: quiet
    engine2 = SloEngine(SloSpec.from_obj({"max_outlier_uploads": 10}))
    assert engine2.evaluate(0, digest, {}) == []


# ---------------------------------------------------------------------------
# defended muxed-vs-per-process determinism (real OS processes)


def _final_leaf_digest(path):
    z = np.load(path)
    h = hashlib.sha256()
    for k in sorted(k for k in z.files if k.startswith("leaf_")):
        h.update(np.ascontiguousarray(z[k]).tobytes())
    return h.hexdigest(), int(z["rounds"])


def test_defended_federation_muxed_vs_per_process_identical(tmp_path):
    """Same seed, streaming defense with the clip ACTIVE (bound below
    the honest delta norm), muxed vs one-process-per-client topology:
    final models byte-identical — the defended twin of the PR-10
    muxed-vs-per-process pin."""
    import os

    from fedml_tpu.experiments.distributed_fedavg import launch

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    digests = {}
    for name, muxers in (("proc", 0), ("mux", 2)):
        out = str(tmp_path / f"final_{name}.npz")
        rc = launch(
            num_clients=4, rounds=2, seed=0, batch_size=16,
            out_path=out, muxers=muxers, env=env,
            defense="streaming", norm_bound=0.1, outlier_mult=50.0,
            timeout=240.0,
        )
        assert rc == 0
        digests[name], rounds = _final_leaf_digest(out)
        assert rounds == 2
    assert digests["proc"] == digests["mux"]


def test_robust_counters_registered_in_metric_schema():
    from fedml_tpu.obs import metric_schema as ms

    for name in ("robust.clipped_uploads", "robust.dp_noised_uploads",
                 "robust.capped_conns", "robust.cap_infeasible"):
        assert ms.metric_type(name) == "counter"
    assert ms.metric_type("robust.upload_norm") == "histogram"


def test_defense_rec_serializable():
    """round_log defense records must be JSON-able (they ride the out
    npz round_log and the round_close telemetry event)."""
    cfg = DefenseConfig(defense="streaming", norm_bound=0.5)
    server = _mk_server(cfg, num_clients=2, clients_per_round=2)
    big = {"params": {"w": np.full((4, 3), 1.0, np.float32),
                      "b": np.zeros((3,), np.float32)}}
    _upload(server, 1, big, 1.0)
    _upload(server, 2, big, 1.0)
    assert server.round_idx == 1
    json.dumps(server.round_log)
    assert server.round_log[-1]["defense"]["clipped"] == 2
