#!/usr/bin/env python
"""Chaos soak driver: run a fault matrix over the multi-process TCP
federation and record per-scenario outcomes.

Each scenario spawns the REAL process topology (hub + server + N client
OS processes over sockets, ``experiments/distributed_fedavg.launch``)
and injects one failure mode; the federation must survive to the final
round with a finite global model.  Default matrix:

    fault_free           no injection — the accuracy baseline
    client_crash         a SAMPLED client os._exit()s at round 1
                         (SIGKILL semantics: no FINISH, dangling socket)
    hub_restart          the hub is SIGKILLed mid-run and restarted on
                         the same port; every worker must re-dial
    drop30               every client's model frames (send+recv) drop
                         with p=0.3 (seeded ``FaultPlan`` via the
                         FEDML_TPU_CHAOS env)
    straggler_deadline   one client sleeps past the round deadline
                         every round — permanently dropped
    corrupt_payload      one client's uploads are NaN-corrupted every
                         round; the server must reject them pre-
                         aggregation
    stripe_faults        striped broadcast, 1 KiB stripes: one node
                         loses a stripe (gap), another gets a corrupted
                         one (crc) — each must cost exactly one node's
                         sync (deadline straggler), never a wedged
                         reassembly
    muxer_crash          half the cohort rides ONE muxer process
                         (virtual-client multiplexing) that os._exit()s
                         at round 1 — hundreds of clients (here: half
                         the federation) vanish in one SIGKILL-shaped
                         event; the spares/stale firewall and the den>0
                         empty-round guard must keep the survivors
                         NaN-free and the degradation visible
                         (rounds.degraded)
    telemetry_loss       one node loses every digest frame; rounds
                         untouched, the SLO report names the dark node
    malicious_client     one client uploads x-25 scaled-gradient
                         mutations every round; the streaming defense's
                         outlier reject must exclude them (counted
                         faults.observed{kind=outlier_upload})
    malicious_muxer      one muxer sign-flips its WHOLE virtual
                         cohort's uploads (the PR-10 Sybil surface);
                         norm clipping + per-connection contribution
                         caps must keep the aggregate finite
    shm_ring_full        shm lane with a 1 MiB ring under a 2 MB model:
                         EVERY model payload exceeds the ring, so every
                         frame must take the counted per-frame TCP
                         fallback — the run completes with zero stalls
                         (the genuine ring_full/desc_full reasons are
                         pinned at unit level in tests/test_shm.py)
    shm_peer_crash       muxer on an shm lane os._exit()s mid-round:
                         the hub's lane detach must look exactly like a
                         dropped connection — survivors aggregate,
                         degraded rounds, never a wedged slab
    edge_hub_crash       two-tier topology: the FIRST edge hub
                         os._exit()s when round 1's sync arrives — a
                         whole cohort (its local hub, its partial fold,
                         its uplink) vanishes in one SIGKILL-shaped
                         event; the root's deadline closes the round on
                         the surviving edge's partials, degradation
                         visible, NaN-free to the final round
    flapping_client      open-loop traffic engine: the muxed cohort's
                         connection flaps (drop + re-hello mid-run, PR
                         13's rebind primitive) and nodes churn
                         offline per round — rounds degrade by
                         deadline, never wedge
    overload_burst       traffic engine at the diurnal peak: arrival
                         delays + heavy-tailed straggler draws spike
                         together mid-run; the deadline (sync) or cut
                         (async) absorbs the burst NaN-free
    compound_crash_telemetry
                         TWO simultaneous faults: a sampled client
                         crashes at round 1 WHILE another node's digest
                         stream is blacked out — the forensics verdict
                         SET must attribute both (client_crash AND
                         telemetry_loss), not just the dominant one

    ``--lane shm`` / ``--bcast delta`` re-run the WHOLE matrix over the
    new transport path (FEDXPORT acceptance: all prior scenarios
    NaN-free over shm+delta); ``--topology tree --edge-hubs N`` re-runs
    it over the hierarchical aggregation tree (PR 17 acceptance: every
    fault mode that held flat must hold with an edge tier terminating
    the cohort — scenario-pinned keys still win, so edge_hub_crash is
    a tree run even in the default flat matrix).

Per scenario the output records: survived, rounds completed, rounds
aggregated empty (``zero_participant_rounds``), degraded rounds,
rejected uploads, fault counters (server process + hub), final test
accuracy and its delta vs the fault-free arm, and a NaN check over the
final global model.

Usage (CPU is fine — this is a protocol soak, not a perf benchmark):

    python tools/chaos_run.py --matrix default --out FAULTS_r06.json
    python tools/chaos_run.py --scenario corrupt_payload
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _worker_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # keep the children lean: no faked mesh
    return env


def _scenarios(round_timeout: float, num_clients: int = 3):
    """name -> launch() kwargs.  Every faulted arm runs with a round
    deadline: without one a single lost upload wedges the federation
    forever (the exact failure mode this subsystem exists to kill)."""
    from fedml_tpu.faults import FaultPlan, FaultRule, FaultSpec
    from fedml_tpu.faults.traffic import TrafficModel

    drop_plan = FaultPlan(
        seed=0,
        send_spec=FaultSpec(drop_prob=0.3),
        recv_spec=FaultSpec(drop_prob=0.3),
        roles=("client",),
    ).to_json()
    corrupt_plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="corrupt", node=3,
                         msg_type="C2S_SEND_MODEL", direction="send")],
        roles=("client",),
    ).to_json()
    # stripe-level faults on the striped broadcast path, harshest
    # sustained form: node 2 loses EVERY sync stripe (never assembles a
    # sync — a full broadcast blackout) and node 3 gets every stripe
    # corrupted (crc mismatch aborts each round's frame).  Both nodes
    # must degrade to deadline stragglers round after round without
    # wedging reassembly or the federation.  The surgical single-stripe
    # cases (one dropped stripe -> gap abort, one corrupted -> crc
    # abort, logical frame dies, connection survives) are pinned at
    # unit level in tests/test_comm.py.
    stripe_plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="drop", node=2,
                         msg_type="S2C_SYNC_MODEL", direction="stripe"),
               FaultRule(action="corrupt", node=3,
                         msg_type="S2C_SYNC_MODEL", direction="stripe")],
        roles=("client",),
    ).to_json()
    # stats-plane blackout: node 2 loses EVERY digest frame it emits
    # (C2S_TELEMETRY is outside DEFAULT_FAULTABLE, so the explicit rule
    # is the only way observability loss happens — never as a side
    # effect of a model-frame mix).  Rounds must be untouched and the
    # rollup un-wedged; the SLO report must flag node 2 as MISSING
    # coverage (counted + named, never silent).
    telemetry_plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="drop", node=2,
                         msg_type="C2S_TELEMETRY", direction="send")],
        roles=("client",),
    ).to_json()
    # Byzantine arms (fedml_tpu/robust): a scaled-gradient malicious
    # client (x-25: sign-flipped AND amplified — norm ~25x honest, so
    # the streaming outlier reject must fire every round), and a
    # malicious MUXER sign-flipping its whole virtual cohort's uploads
    # through one connection (the PR-10 Sybil surface) — conn caps +
    # norm clipping must bound it.  Both finite: the non-finite
    # firewall never sees them; only the defense layer can.
    malicious_client_plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="scale_grad", node=3,
                         msg_type="C2S_SEND_MODEL", direction="send",
                         attack_scale=-25.0)],
        roles=("client",),
    ).to_json()
    muxed_half = (num_clients + 1) // 2
    malicious_muxer_plan = FaultPlan(
        seed=0,
        rules=[FaultRule(action="sign_flip", node=n,
                         msg_type="C2S_SEND_MODEL", direction="send")
               for n in range(1, muxed_half + 1)],
        roles=("client",),
    ).to_json()
    # open-loop traffic arms (faults/traffic.py): seeded arrival
    # processes shipped via FEDML_TPU_TRAFFIC — a deterministic day of
    # churn, not a flake.  Probabilities are per (node x round).
    flapping_traffic = TrafficModel(
        seed=0, jitter_s=0.1, churn_prob=0.25, flap_prob=0.5,
    ).to_json()
    # diurnal peak: amplitude 1 on a 2-round period puts every other
    # round at ~2x load — delays and heavy-tailed straggler draws spike
    # together; the straggler cap stays well under the round deadline
    # so most late uploads still arrive (and in async mode fold at the
    # staleness discount) instead of all vanishing at once
    burst_traffic = TrafficModel(
        seed=0, jitter_s=0.1, straggler_prob=0.6,
        straggler_scale_s=0.3, straggler_cap_s=2.0,
        diurnal_amplitude=1.0, diurnal_period_rounds=2,
    ).to_json()
    return {
        "fault_free": {},
        "client_crash": {
            "crash_client_at_round": 1,
            "round_timeout": round_timeout,
        },
        "hub_restart": {
            "restart_hub_after": 1.0,
            "auto_reconnect": 60,
            "round_timeout": round_timeout,
        },
        "drop30": {
            "chaos_plan": drop_plan,
            "round_timeout": round_timeout,
        },
        "straggler_deadline": {
            "slow_client_delay": 10 * round_timeout,
            "round_timeout": round_timeout,
        },
        "corrupt_payload": {
            "chaos_plan": corrupt_plan,
            "round_timeout": round_timeout,
        },
        "stripe_faults": {
            "chaos_plan": stripe_plan,
            "round_timeout": round_timeout,
            # 1 KiB stripes AND a model big enough to cross the
            # threshold: the default 8-dim model's ~450 B sync payload
            # never striped, so this scenario silently injected NOTHING
            # from PR 9 through PR 13 (every FAULTS_r*.json shows
            # degraded=0 and an empty fault-counter set) — caught by
            # the r16 forensics pass when the bundle-only verdict came
            # back "none".  8.2 KB model -> every sync is ~8 stripes.
            "stripe_kib": 1,
            "input_dim": 1024,
        },
        # killing one muxer drops its WHOLE virtual cohort at once (in
        # production: hundreds of clients; here: half the federation —
        # clients 1..ceil(N/2) ride the one muxer, the rest run as
        # plain processes so the survivors keep reporting).  The rounds
        # after the crash must close degraded by deadline with finite
        # aggregates, never NaN or a wedge.
        "muxer_crash": {
            "muxers": 1,
            "muxed_clients": -1,  # resolved to ceil(N/2) in run_scenario
            "crash_muxer_at_round": 1,
            "round_timeout": round_timeout,
        },
        # dropped digest frames must never affect rounds or wedge the
        # rollup: the run completes normally while the SLO report flags
        # the silenced node (run_dir="auto" -> a tmpdir; run_scenario
        # reads slo_report.json back as scenario evidence)
        "telemetry_loss": {
            "chaos_plan": telemetry_plan,
            "round_timeout": round_timeout,
            "run_dir": "auto",
            # short staleness threshold so the blacked-out node trips
            # the coverage objective within this few-round run (the
            # engine's startup grace = one threshold of uptime)
            "slo": json.dumps({"max_stale_streams": 0,
                               "stale_after_s": 1.5}),
        },
        # the x-25 attacker's every upload must be outlier-rejected
        # (counted, never folded), the round closing by deadline with
        # the honest reporters — accuracy within noise of fault_free
        "malicious_client": {
            "chaos_plan": malicious_client_plan,
            "round_timeout": round_timeout,
            "defense": "streaming",
            "norm_bound": 2.0,
            "outlier_mult": 3.0,
        },
        # one muxer sign-flips its whole co-located cohort (half the
        # federation) through ONE connection: norm clipping bounds each
        # upload, the conn cap bounds the connection's total weight —
        # the aggregate must stay finite and the run NaN-free
        # conn_cap 0.5, not lower: at 3 clients the topology has only
        # TWO client connections (the muxer + one dialer), and a cap
        # below 1/2 is unsatisfiable by construction — the engine
        # refuses it loudly (robust.cap_infeasible) rather than
        # half-applying.  norm_bound 1.0 (~5x the honest delta norm):
        # a clipped sign-flip cannot cross zero, only shrink.
        "malicious_muxer": {
            "muxers": 1,
            "muxed_clients": -1,  # resolved to ceil(N/2) in run_scenario
            "chaos_plan": malicious_muxer_plan,
            "round_timeout": round_timeout,
            "defense": "streaming",
            "norm_bound": 1.0,
            "outlier_mult": 6.0,
            "conn_cap": 0.5,
        },
        # every 2.1 MB model payload overflows the 1 MiB/direction ring:
        # the lane must take the counted per-frame TCP fallback every
        # time and the federation must finish with no stall (hub_stats
        # + server shm counters carry the evidence)
        "shm_ring_full": {
            "lane": "shm",
            "shm_mib": 1,
            "shm_min_bytes": 0,
            "input_dim": 262144,
            "round_timeout": round_timeout,
        },
        # a muxer whose payloads ride an shm lane dies mid-round: slab
        # detach == dropped connection (doorbells stop, hub cleans up),
        # survivors keep aggregating — the muxer_crash contract over
        # the new lane
        "shm_peer_crash": {
            "lane": "shm",
            "shm_min_bytes": 0,
            "muxers": 1,
            "muxed_clients": -1,  # resolved to ceil(N/2) in run_scenario
            "crash_muxer_at_round": 1,
            "round_timeout": round_timeout,
        },
        # the FIRST edge hub of a two-edge tree hard-exits when round
        # 1's sync arrives: its whole cohort is orphaned at once (their
        # local hub died under them — reconnects dial a dead port).
        # The root must close every later round by deadline on the
        # surviving edge's partials: degraded rounds, finite model,
        # rc=0.  Topology keys are pinned HERE so the scenario is a
        # tree run even inside the default flat matrix.
        "edge_hub_crash": {
            "topology": "tree",
            "edge_hubs": 2,
            "crash_edge_hub_at_round": 1,
            "round_timeout": round_timeout,
        },
        # churn mid-round via the traffic engine: the muxed half-cohort
        # flaps its ONE connection (drop + re-hello between rounds —
        # PR 13's rebind_connection) while nodes churn offline per
        # round; the reconnect machinery absorbs the flaps and the
        # deadline closes churned rounds degraded, never wedged
        "flapping_client": {
            "muxers": 1,
            "muxed_clients": -1,  # resolved to ceil(N/2) in run_scenario
            "traffic_plan": flapping_traffic,
            "auto_reconnect": 60,
            "round_timeout": round_timeout,
        },
        # arrival spike at the diurnal peak: every node's delay +
        # straggler draw inflates together on peak rounds — the
        # deadline (sync) or the cut + staleness discount (async) must
        # absorb the burst with finite aggregates
        "overload_burst": {
            "traffic_plan": burst_traffic,
            "round_timeout": round_timeout,
        },
        # TWO simultaneous faults: the last sampled client hard-exits
        # at round 1 WHILE node 2's digest stream is blacked out.  The
        # forensics verdict SET must attribute both (client_crash AND
        # telemetry_loss) — the compound-attribution contract
        "compound_crash_telemetry": {
            "crash_client_at_round": 1,
            "chaos_plan": telemetry_plan,
            "round_timeout": round_timeout,
            "slo": json.dumps({"max_stale_streams": 0,
                               "stale_after_s": 1.5}),
        },
    }


def _final_model_eval(out_path: str, seed: int, num_clients: int,
                      input_dim: int = 8):
    """Load the server's final leaves and evaluate on the shared
    synthetic test split (every process builds the same problem from the
    seed, so this is the federation's real held-out accuracy)."""
    import numpy as np

    import jax

    from fedml_tpu.core.client import eval_summary, make_evaluator
    from fedml_tpu.core.types import batch_eval_pack
    from fedml_tpu.experiments.distributed_fedavg import _build_problem

    ds, bundle, init, _ = _build_problem(seed, num_clients,
                                         input_dim=input_dim)
    leaves_like, treedef = jax.tree_util.tree_flatten(init)
    z = np.load(out_path)
    leaves = [np.asarray(z[f"leaf_{i}"]) for i in range(len(leaves_like))]
    nan_free = bool(all(np.isfinite(l).all() for l in leaves))
    variables = jax.tree_util.tree_unflatten(treedef, leaves)
    x, y, m = batch_eval_pack(ds.test_x, ds.test_y, 32)
    summary = eval_summary(make_evaluator(bundle)(variables, x, y, m))
    round_log = json.loads(str(z["round_log"]))
    return {
        "nan_free": nan_free,
        "final_acc": float(summary["test_acc"]),
        "final_loss": float(summary["test_loss"]),
        "rounds_recorded": int(z["rounds"]),
        "round_participants": [
            r.get("participants") for r in round_log if "participants" in r
        ],
    }


def _forensics(run_dir: str) -> dict:
    """Postmortem verdict over the scenario's flight-recorder bundles
    (``tools/fed_forensics.py``) — the scenario record's evidence that
    the black box alone names the injected fault."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import fed_forensics

        v = fed_forensics.analyze(run_dir)
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    return {
        "fault_kind": v.get("fault_kind"),
        "fault_round": v.get("fault_round"),
        "confidence": v.get("confidence"),
        "clock_mode": v.get("clock_mode"),
        "evidence": v.get("evidence"),
        # the RANKED verdict set (compound faults get one entry each);
        # the top-level fields above are its dominant entry
        "verdicts": [
            {"fault_kind": c.get("fault_kind"),
             "fault_round": c.get("fault_round"),
             "confidence": c.get("confidence")}
            for c in (v.get("verdicts") or ())
        ],
        "bundle_errors": v.get("bundle_errors"),
    }


def run_scenario(name: str, kwargs: dict, *, num_clients: int, rounds: int,
                 seed: int, timeout: float, transport=None) -> dict:
    from fedml_tpu.experiments.distributed_fedavg import launch

    if transport:
        # matrix-wide transport overrides (--lane/--bcast): scenario-
        # specific keys win (the shm scenarios pin their own lane)
        kwargs = {**transport, **kwargs}

    out_path = os.path.join(
        tempfile.mkdtemp(prefix=f"chaos_{name}_"), "final.npz"
    )
    if kwargs.get("muxed_clients") == -1:
        kwargs = dict(kwargs, muxed_clients=(num_clients + 1) // 2)
    if not kwargs.get("run_dir") or kwargs.get("run_dir") == "auto":
        # every scenario gets a run_dir now: the flight recorders in
        # each child process dump their black-box bundles there, and
        # the record below carries the forensics verdict built from
        # them (telemetry_loss additionally reads slo_report.json back)
        kwargs = dict(kwargs, run_dir=os.path.dirname(out_path))
    run_dir = kwargs["run_dir"]
    info: dict = {}
    t0 = time.time()
    print(f"== scenario {name} ==", flush=True)
    try:
        rc = launch(
            num_clients=num_clients, rounds=rounds, seed=seed,
            batch_size=16, out_path=out_path, env=_worker_env(),
            info=info, timeout=timeout, **kwargs,
        )
    except Exception as e:  # harness failure IS a scenario failure
        return {"scenario": name, "survived": False,
                "error": f"{type(e).__name__}: {e}",
                "flight_bundles": sorted(
                    glob.glob(os.path.join(run_dir, "flight-*.json"))),
                "forensics": _forensics(run_dir),
                "wall_s": round(time.time() - t0, 1)}
    rec = {
        "scenario": name,
        "survived": rc == 0,
        "rc": rc,
        "rounds": info.get("rounds"),
        "rounds_aggregated_empty": info.get("zero_participant_rounds"),
        "rounds_degraded": info.get("rounds_degraded"),
        "rejected_uploads": info.get("rejected_uploads"),
        "server_fault_counters": info.get("faults") or {},
        "hub_stats": info.get("hub_stats") or {},
        "stats_plane": info.get("stats_plane") or {},
        "wall_s": round(time.time() - t0, 1),
    }
    rec["flight_bundles"] = sorted(
        glob.glob(os.path.join(run_dir, "flight-*.json")))
    rec["forensics"] = _forensics(run_dir)
    report_path = os.path.join(os.path.dirname(out_path), "slo_report.json")
    if kwargs.get("run_dir") and os.path.exists(report_path):
        # telemetry-loss evidence: the SLO report must NAME the node(s)
        # whose digest stream went dark (missing coverage), while the
        # round outcome above stays untouched
        try:
            with open(report_path) as fh:
                rep = json.load(fh)
            sp = rep.get("stats_plane") or {}
            rec["slo_report"] = {
                "ok": rep.get("ok"),
                "by_objective": rep.get("by_objective"),
                "missing_nodes": sp.get("missing_nodes"),
                "stale_streams": sp.get("stale_streams"),
                "streams": sp.get("streams"),
            }
        except (OSError, json.JSONDecodeError) as e:
            rec["slo_report"] = {"error": f"{type(e).__name__}: {e}"}
    if os.path.exists(out_path):
        try:
            rec.update(_final_model_eval(out_path, seed, num_clients,
                                         kwargs.get("input_dim", 8)))
        except Exception as e:
            rec["eval_error"] = f"{type(e).__name__}: {e}"
            rec["nan_free"] = False
    print(f"   -> rc={rc} acc={rec.get('final_acc')} "
          f"empty_rounds={rec.get('rounds_aggregated_empty')} "
          f"({rec['wall_s']}s)", flush=True)
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--matrix", default="default", choices=["default"])
    p.add_argument("--scenario", default="",
                   help="run one scenario by name instead of the matrix")
    p.add_argument("--out", default="FAULTS_r06.json")
    p.add_argument("--num-clients", type=int, default=3)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--round-timeout", type=float, default=20.0,
                   help="per-round deadline for the faulted arms; must "
                        "exceed a client's cold jit+train time on the "
                        "host (~5-10 s on a loaded 1-core CI box)")
    p.add_argument("--timeout", type=float, default=240.0,
                   help="per-scenario hard cap on the server process")
    # transport-path overrides: soak the WHOLE matrix over the shm lane
    # and/or the delta broadcast (FEDXPORT acceptance re-run); the tiny
    # chaos model's frames only exercise the lane at --shm-min-bytes 0
    p.add_argument("--lane", choices=["tcp", "shm"], default="tcp")
    p.add_argument("--bcast", choices=["full", "delta"], default="full")
    p.add_argument("--shm-min-bytes", type=int, default=0)
    # topology override: soak the whole matrix over the hierarchical
    # aggregation tree (PR 17) — scenario-pinned keys still win
    p.add_argument("--topology", choices=["flat", "tree"], default="flat")
    p.add_argument("--edge-hubs", type=int, default=2)
    # round-mode override: soak the whole matrix over the async
    # buffered server (fold-on-arrival, cut-based rounds, staleness
    # discounts) — every fault mode that held under the barrier must
    # hold under cuts
    p.add_argument("--round-mode", choices=["sync", "async"],
                   default="sync")
    p.add_argument("--max-staleness", type=int, default=2)
    args = p.parse_args(argv)

    scenarios = _scenarios(args.round_timeout, args.num_clients)
    if args.scenario:
        if args.scenario not in scenarios:
            print(f"unknown scenario {args.scenario!r}; "
                  f"have {sorted(scenarios)}", file=sys.stderr)
            return 2
        scenarios = {args.scenario: scenarios[args.scenario]}

    transport = {}
    if args.lane != "tcp":
        transport["lane"] = args.lane
        transport["shm_min_bytes"] = args.shm_min_bytes
    if args.bcast != "full":
        transport["bcast"] = args.bcast
    if args.topology == "tree":
        transport["topology"] = "tree"
        transport["edge_hubs"] = args.edge_hubs
    if args.round_mode != "sync":
        transport["round_mode"] = args.round_mode
        transport["max_staleness"] = args.max_staleness

    results = []
    for name, kwargs in scenarios.items():
        results.append(run_scenario(
            name, kwargs, num_clients=args.num_clients, rounds=args.rounds,
            seed=args.seed, timeout=args.timeout, transport=transport,
        ))

    baseline = next(
        (r for r in results
         if r["scenario"] == "fault_free" and "final_acc" in r), None
    )
    for r in results:
        if baseline is not None and "final_acc" in r:
            r["acc_delta_vs_fault_free"] = round(
                r["final_acc"] - baseline["final_acc"], 6
            )

    doc = {
        "matrix": args.matrix if not args.scenario else args.scenario,
        "lane": args.lane,
        "bcast": args.bcast,
        "round_mode": args.round_mode,
        "num_clients": args.num_clients,
        "rounds": args.rounds,
        "seed": args.seed,
        "round_timeout_s": args.round_timeout,
        "generated_unix": round(time.time(), 1),
        "scenarios": results,
        "all_survived": all(r.get("survived") for r in results),
        "all_nan_free": all(r.get("nan_free", False) for r in results),
    }
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2)
    print(json.dumps({"out": args.out,
                      "all_survived": doc["all_survived"],
                      "all_nan_free": doc["all_nan_free"]}))
    return 0 if doc["all_survived"] and doc["all_nan_free"] else 1


if __name__ == "__main__":
    sys.exit(main())
