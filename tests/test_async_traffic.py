"""Async buffered rounds + open-loop traffic engine (ISSUE 18): the
staleness-weight math vs a numpy oracle, the seeded traffic model's
replay determinism, the async==sync byte-identity pin (w == 1 with
synchronized arrivals makes cut-based rounds EXACTLY the barrier —
fp32 and int8+EF, in-process and muxed), cut-size round cuts, the
staleness SLO objectives, and the forensics ranked-verdict set over a
two-fault bundle fixture."""

import json
import math
import os
import random
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.fedavg_cross_device import (
    FedAvgClientManager,
    FedAvgServerManager,
)
from fedml_tpu.comm.inproc import InprocBus
from fedml_tpu.core.client import make_client_optimizer, make_local_update
from fedml_tpu.core.staleness import (
    STALENESS_POLICIES,
    effective_weight,
    staleness_weight,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.faults.traffic import ENV_VAR, TrafficModel
from fedml_tpu.models.linear import logistic_regression
from fedml_tpu.obs import digest as dg
from fedml_tpu.obs.slo import SloEngine, SloSpec
from fedml_tpu.obs.telemetry import Telemetry, get_telemetry

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tools"))

import fed_forensics  # noqa: E402


# --- staleness-weight math vs numpy oracle ----------------------------------

def test_staleness_weight_poly_matches_numpy_oracle():
    deltas = np.array([0.0, 1.0, 2.0, 5.0, 17.0])
    for alpha in (0.25, 0.5, 1.0, 2.0):
        oracle = (1.0 + deltas) ** (-alpha)
        got_np = staleness_weight(deltas, "poly", alpha=alpha, xp=np)
        got_jnp = staleness_weight(deltas, "poly", alpha=alpha)
        np.testing.assert_allclose(np.asarray(got_np), oracle, rtol=0)
        # the jnp arm agrees to float32 (no x64 on the default config);
        # the identity anchor below is exact in BOTH arms regardless
        np.testing.assert_allclose(np.asarray(got_jnp),
                                   np.asarray(got_np), rtol=1e-6)
    # w == 1 is exact in the jnp arm too (x**0 == 1.0 in every dtype)
    w1 = staleness_weight(np.array([0.0, 4.0, 50.0]), "poly", alpha=0.0)
    assert np.asarray(w1).tolist() == [1.0, 1.0, 1.0]


def test_staleness_weight_identity_and_clamps():
    # alpha=0 is the byte-identity anchor: EXACTLY 1.0 at every delta,
    # never an approximation (IEEE x**0 == 1.0)
    w = staleness_weight(np.array([0.0, 3.0, 99.0]), "poly", alpha=0.0,
                         xp=np)
    assert np.asarray(w).tolist() == [1.0, 1.0, 1.0]
    # a fresh upload (delta 0) is never discounted by either policy
    for policy in STALENESS_POLICIES:
        assert float(staleness_weight(0, policy, xp=np)) == 1.0
    # negative deltas (clock skew in a caller) clamp to fresh
    assert float(staleness_weight(-3, "poly", alpha=0.5, xp=np)) == 1.0


def test_staleness_weight_const_window_and_effective():
    w = staleness_weight(np.array([0.0, 1.0, 2.0, 3.0]), "const",
                         window=2, xp=np)
    assert np.asarray(w).tolist() == [1.0, 1.0, 1.0, 0.0]
    # effective_weight folds the example count in: n * w(delta)
    ew = effective_weight(80, 1, "poly", alpha=1.0, xp=np)
    assert float(ew) == pytest.approx(40.0)
    with pytest.raises(ValueError):
        staleness_weight(1, "exponential", xp=np)
    with pytest.raises(ValueError):
        staleness_weight(1, "poly", alpha=-0.5, xp=np)


# --- open-loop traffic model: seeded replay determinism ---------------------

def _traffic(seed=0):
    return TrafficModel(seed=seed, jitter_s=0.05, straggler_prob=0.3,
                        straggler_shape=1.1, straggler_scale_s=0.3,
                        straggler_cap_s=2.0, churn_prob=0.1,
                        flap_prob=0.05, diurnal_amplitude=0.5,
                        diurnal_period_rounds=4)


def test_traffic_schedule_replays_bit_identically():
    """Same seed => the full (node x round) decision trace is
    byte-identical, across fresh instances AND a JSON ship-and-parse
    round trip (the exact path a plan takes into worker processes)."""
    nodes, rounds = list(range(1, 17)), 6
    a, b = _traffic(), TrafficModel.from_json(_traffic().to_json())
    for r in range(rounds):
        for n in nodes:
            assert a.decide(n, r) == b.decide(n, r)
    assert a.schedule_digest(nodes, rounds) \
        == b.schedule_digest(nodes, rounds)
    # decide() is PURE: a second call returns the identical decision
    # (no hidden RNG state advanced between calls)
    assert a.decide(3, 2) == a.decide(3, 2)
    # a reseeded day is a different day
    assert _traffic(1).schedule_digest(nodes, rounds) \
        != a.schedule_digest(nodes, rounds)


def test_traffic_speed_class_sticky_and_delays_bounded():
    tm = _traffic()
    for n in (1, 5, 9):
        assert tm.speed_class(n) == tm.speed_class(n)  # per-lifetime
    cap = tm.straggler_cap_s
    worst_mult = max(m for _, _, m in tm.speed_classes)
    worst_diurnal = 1.0 + tm.diurnal_amplitude
    bound = (tm.jitter_s + cap) * worst_mult * worst_diurnal + 1e-9
    for r in range(4):
        for n in range(1, 33):
            d = tm.decide(n, r)
            assert 0.0 <= d["delay_s"] <= bound
            assert d["class"] in {c for c, _, _ in tm.speed_classes}


def test_traffic_diurnal_curve_and_env_roles(monkeypatch):
    tm = TrafficModel(seed=0, diurnal_amplitude=1.0,
                      diurnal_period_rounds=4)
    # sin curve over the period: trough clamps at 0, crest at 1 + A
    assert tm.diurnal_factor(0) == pytest.approx(1.0)
    assert tm.diurnal_factor(1) == pytest.approx(2.0)
    assert tm.diurnal_factor(3) == pytest.approx(0.0)
    # env ride: same JSON contract as FEDML_TPU_CHAOS, gated by role
    plan = TrafficModel(seed=3, jitter_s=0.1, roles=("muxer",))
    monkeypatch.setenv(ENV_VAR, plan.to_json())
    got = TrafficModel.from_env()
    assert got is not None and got.to_json() == plan.to_json()
    assert "client" not in got.roles
    monkeypatch.delenv(ENV_VAR)
    assert TrafficModel.from_env() is None
    # a plan with every knob zeroed is no traffic at all
    assert not TrafficModel(seed=0).any_traffic()


# --- async == sync byte-identity (the acceptance anchor) --------------------

def _problem(seed=0):
    ds = synthetic_classification(
        num_train=240, num_test=60, input_shape=(16,), num_classes=4,
        num_clients=3, partition="hetero", partition_alpha=0.4, seed=seed)
    bundle = logistic_regression(16, 4)
    init = bundle.init(jax.random.PRNGKey(seed))
    lu = make_local_update(
        bundle, make_client_optimizer("sgd", 0.1, momentum=0.9), 1)
    steps = int(np.ceil(ds.client_sample_counts().max() / 16))
    return ds, init, lu, steps


def _run_inproc(seed=0, **server_kw):
    ds, init, lu, steps = _problem(seed)
    bus = InprocBus()
    server = FedAvgServerManager(
        bus.register(0), init, num_clients=3, clients_per_round=3,
        comm_rounds=3, seed=seed, steps_per_epoch=steps, **server_kw)
    for i in range(3):
        FedAvgClientManager(bus.register(i + 1), lu, ds, batch_size=16,
                            template_variables=init, seed=seed)
    server.start()
    bus.drain()
    return server


def _leaves_bytes(server):
    return [np.asarray(l).tobytes()
            for l in jax.tree_util.tree_leaves(server.variables)]


@pytest.mark.parametrize("codec", ["none", "qsgd8"])
def test_async_equals_sync_byte_identical_inproc(codec):
    """w == 1 (stale_alpha=0) + synchronized arrivals: the async cut is
    EXACTLY the barrier — same seed, final models byte-identical, for
    fp32 full models and int8+EF deltas alike."""
    sync = _run_inproc(codec=codec)
    asyn = _run_inproc(codec=codec, round_mode="async", stale_alpha=0.0)
    assert _leaves_bytes(sync) == _leaves_bytes(asyn)
    assert asyn.round_idx == sync.round_idx


def test_async_cut_size_cuts_early_and_counts():
    tel = get_telemetry()
    before = tel.snapshot()["counters"].get("async.cut_rounds", 0)
    server = _run_inproc(round_mode="async", cut_size=2,
                         round_timeout=10.0)
    assert server.round_idx == 3
    after = tel.snapshot()["counters"].get("async.cut_rounds", 0)
    assert after - before == 3  # every round closed at the K-cut
    # cut at K=2 of 3: each round folds AT LEAST the cut target (the
    # third arrival lands as next-round staleness-1 fold or a late
    # same-round arrival, never a loss)
    rounds = [r for r in server.round_log if "participants" in r]
    assert all(len(r["participants"]) >= 2 for r in rounds)
    for leaf in jax.tree_util.tree_leaves(server.variables):
        assert np.isfinite(np.asarray(leaf)).all()


def test_async_requires_streaming_fold():
    ds, init, lu, steps = _problem()
    bus = InprocBus()
    with pytest.raises(ValueError, match="streaming"):
        FedAvgServerManager(
            bus.register(0), init, num_clients=3, clients_per_round=3,
            comm_rounds=1, streaming_agg=False, round_mode="async")
    with pytest.raises(ValueError, match="round_mode"):
        FedAvgServerManager(
            bus.register(0), init, num_clients=3, clients_per_round=3,
            comm_rounds=1, round_mode="bulk")


# --- staleness SLO objectives -----------------------------------------------

def test_slo_staleness_and_discarded_weight_objectives():
    tel = Telemetry()
    eng = SloEngine(SloSpec(p99_upload_staleness=1.0,
                            max_discarded_weight_frac=0.2),
                    telemetry=tel)
    tel.observe("async.upload_staleness", 0.0)
    tel.observe("async.upload_staleness", 3.0)  # p99 -> bucket above 1
    tel.inc("async.folded_weight", 60.0)
    tel.inc("async.discarded_weight", 40.0)  # frac 0.4 > 0.2
    rollup = dg.registry_digest(tel, t=1.0)
    new = eng.evaluate(0, rollup, {})
    objectives = {v["objective"] for v in new}
    assert objectives == {"upload_staleness_p99", "discarded_weight_frac"}
    rep = eng.report(rollup, {})
    assert rep["ok"] is False
    assert rep["observed"]["discarded_weight_frac"] \
        == pytest.approx(0.4)
    assert rep["observed"]["upload_staleness"]["count"] == 2
    # healthy run: no async traffic at all -> objectives do not fire
    tel2 = Telemetry()
    eng2 = SloEngine(SloSpec(p99_upload_staleness=1.0,
                             max_discarded_weight_frac=0.2),
                     telemetry=tel2)
    assert eng2.evaluate(0, dg.registry_digest(tel2, t=1.0), {}) == []
    assert eng2.report(dg.registry_digest(tel2, t=1.0), {})[
        "observed"]["discarded_weight_frac"] is None


# --- forensics: ranked verdict SET over a compound fault --------------------

def _write_bundle(run_dir, tag, *, history=(), rings=None, counters=None,
                  t0=1000.0):
    b = {
        "schema": 1, "node": tag, "pid": 1, "window_s": 60.0,
        "trigger": (history[-1] if history
                    else {"kind": "manual", "reason": "", "round": None,
                          "t_m": t0, "t_wall": t0}),
        "history": list(history),
        "clock_sync": None,
        "t_m_dump": t0 + 100.0, "t_wall_dump": t0 + 100.0,
        "telemetry": {"counters": counters or {}, "gauges": {},
                      "hists": {}},
        "rings": dict({"events": [], "hops": [], "spans": [], "comm": [],
                       "faults": [], "locks": [], "notes": []},
                      **(rings or {})),
    }
    Path(run_dir, f"flight-{tag}.json").write_text(json.dumps(b))


def _server_rounds(t0=1000.0, walls=(2.0, 2.0, 2.0)):
    rows, t = [], t0
    for i, w in enumerate(walls):
        rows.append({"t_m": t + w, "kind": "round_close", "round": i,
                     "t_open_m": t, "t_close_m": t + w, "participants": 3})
        t += w
    return rows


def test_forensics_two_fault_bundle_yields_both_verdicts(tmp_path):
    """A crash AND an independent telemetry blackout in one run: the
    verdict is a ranked SET naming both faults with their rounds —
    not a single winner swallowing the other."""
    _write_bundle(tmp_path, "node0",
                  history=[{"kind": "slo_violation",
                            "reason": "stats_plane_coverage", "round": 2,
                            "t_m": 1005.0, "t_wall": 1005.0}],
                  rings={"events": _server_rounds()})
    _write_bundle(tmp_path, "node3", history=[
        {"kind": "crash", "reason": "crash_at_round", "round": 1,
         "t_m": 1002.5, "t_wall": 1002.5}])
    _write_bundle(tmp_path, "node2",
                  counters={"faults.injected{action=drop,"
                            "msg_type=C2S_TELEMETRY}": 4.0})
    v = fed_forensics.analyze(str(tmp_path))
    kinds = {c["fault_kind"]: c for c in v["verdicts"]}
    assert {"client_crash", "telemetry_loss"} <= set(kinds)
    assert kinds["client_crash"]["fault_round"] == 1
    assert kinds["client_crash"]["confidence"] == "high"
    # the top-level verdict mirrors the highest-confidence entry
    assert v["fault_kind"] == v["verdicts"][0]["fault_kind"]
    ranks = [{"high": 0, "medium": 1, "low": 2}[c["confidence"]]
             for c in v["verdicts"]]
    assert ranks == sorted(ranks)
    # single-fault runs still read as one-entry sets (back-compat)
    for f in ("flight-node3.json", "flight-node2.json"):
        os.unlink(tmp_path / f)
    _write_bundle(tmp_path, "node0", rings={"events": _server_rounds()})
    v = fed_forensics.analyze(str(tmp_path))
    assert v["fault_kind"] == "none" and len(v["verdicts"]) == 1


# --- real-process federations ------------------------------------------------

def _fed_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def test_muxed_async_byte_identical_to_sync(tmp_path):
    """The muxed arm of the pin: same seed, w == 1 — a muxed async
    federation's final model equals the muxed sync federation's, byte
    for byte."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    leaves = {}
    for tag, extra in (("sync", {}),
                       ("async", {"round_mode": "async",
                                  "stale_alpha": 0.0})):
        out = str(tmp_path / f"final_{tag}.npz")
        rc = launch(num_clients=3, rounds=2, seed=0, batch_size=16,
                    out_path=out, muxers=1, env=_fed_env(),
                    timeout=240.0, **extra)
        assert rc == 0, f"{tag} federation failed"
        z = np.load(out)
        leaves[tag] = [np.asarray(z[k]) for k in sorted(z.files)
                       if k.startswith("leaf_")]
    for a, b in zip(leaves["sync"], leaves["async"]):
        np.testing.assert_array_equal(a, b)


def test_singleton_flush_lone_straggler_under_open_loop(tmp_path):
    """PR-10's singleton-cohort flush composes with the traffic model:
    one virtual client's sync arrives LATE (timer-thread re-injection
    — no dispatch flush coming), under an active open-loop schedule —
    it trains as a cohort of one and still makes the round."""
    from fedml_tpu.experiments.distributed_fedavg import launch
    from fedml_tpu.faults import FaultPlan, FaultRule

    chaos = FaultPlan(
        seed=0,
        rules=[FaultRule(action="delay", node=3,
                         msg_type="S2C_SYNC_MODEL", direction="recv",
                         delay_s=0.4)],
        roles=("client", "muxer"),
    ).to_json()
    traffic = TrafficModel(seed=0, jitter_s=0.05).to_json()
    out = str(tmp_path / "final_singleton.npz")
    rc = launch(num_clients=3, rounds=2, seed=0, batch_size=16,
                out_path=out, muxers=1, chaos_plan=chaos,
                traffic_plan=traffic, round_timeout=30.0,
                env=_fed_env(), timeout=240.0)
    assert rc == 0
    z = np.load(out)
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    # the delayed node participates in every round — the singleton
    # flush trained it despite missing its cohort's dispatch flush
    assert all(r["participants"] == [1, 2, 3] for r in rounds)


@pytest.mark.slow
def test_async_federation_with_churn_slow(tmp_path):
    """Marked-slow soak: a real muxed federation under the full
    open-loop day (heavy-tailed stragglers + churn + flap) in async
    mode — rounds cut at K arrivals, late work folds discounted, the
    model stays finite and the run exits clean."""
    from fedml_tpu.experiments.distributed_fedavg import launch

    traffic = TrafficModel(
        seed=0, jitter_s=0.05, straggler_prob=0.3, straggler_shape=1.1,
        straggler_scale_s=0.3, straggler_cap_s=2.0, churn_prob=0.1,
        flap_prob=0.05, diurnal_amplitude=0.5,
        diurnal_period_rounds=4).to_json()
    out = str(tmp_path / "final_churn.npz")
    info = {}
    rc = launch(num_clients=16, rounds=3, seed=0, batch_size=16,
                out_path=out, muxers=2, round_mode="async", cut_size=10,
                round_timeout=15.0, traffic_plan=traffic,
                auto_reconnect=60, env=_fed_env(), info=info,
                timeout=420.0)
    assert rc == 0
    z = np.load(out)
    assert int(z["rounds"]) == 3
    for k in z.files:
        if k.startswith("leaf_"):
            assert np.isfinite(z[k]).all()
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert all(len(r["participants"]) >= 10 for r in rounds)
