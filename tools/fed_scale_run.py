#!/usr/bin/env python
"""Virtual-client multiplexing evidence run → ``FEDSCALE_r10.json``.

Two measurements, one artifact:

**scale** — a 10,000-virtual-client federation on THIS box: M muxer
processes (hello v2) drive the whole cohort over M hub connections,
each round trained as one vmapped jit step per muxer.  The hub's peak
RSS (``/proc/<pid>/status`` VmHWM) is recorded for the scale run AND
for a 32-client one-process-per-client reference at the same model
config — the pre-declared bound is scale-hub-RSS < 4x reference
(streaming fold + metadata-only pending keep the hub and server
O(model), not O(clients)).  Per-round wall times come from the
server's ``round_log`` close stamps, exactly the FEDLAT series.

**ab** — the FEDLAT-style latency A/B at 32 virtual clients, PR-6
protocol (ABBA-interleaved reps, process barrier + settle between
runs, verdict = median of per-rep p50s), FEDLAT_r09 configuration
(``logistic_regression(--input-dim 131072, 2)`` ≈ 1 MB model,
``--train-samples 16`` comm-dominant):

    mux          1 muxer × 32 virtual clients (4 OS processes total)
    proc_fast    32 client processes, fast hotpath (FEDLAT_r09's
                 striped arm — the +14% regression this PR attacks)
    proc_legacy  32 client processes, legacy serial unicast (the
                 FEDLAT_r09 baseline the fast path lost to)

Pre-declared bar: muxed p50 ≤ legacy p50.  A 256-virtual-client muxed
run rides along as the scaling datapoint (a 256-process arm does not
fit this box — 257 jax runtimes is an OOM, which is itself the point).

Usage:
    python tools/fed_scale_run.py --mode scale --clients 10000
    python tools/fed_scale_run.py --mode ab --reps 2
    python tools/fed_scale_run.py --mode both --out FEDSCALE_r10.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.trace_summary import percentile  # noqa: E402


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def _vm_kb(pid: int, key: str) -> int:
    """Read one Vm* line (kB) from /proc/<pid>/status; 0 if gone."""
    try:
        with open(f"/proc/{pid}/status") as fh:
            for line in fh:
                if line.startswith(key + ":"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def _round_walls(npz_path: str):
    import numpy as np

    z = np.load(npz_path)
    log = json.loads(str(z["round_log"]))
    stamps = [r["t"] for r in log if isinstance(r.get("t"), (int, float))]
    deltas = [round(b - a, 4) for a, b in zip(stamps, stamps[1:])]
    finite = True
    for k in z.files:
        if k.startswith("leaf_"):
            finite = finite and bool(np.isfinite(z[k]).all())
    return int(z["rounds"]), deltas, finite


def _barrier(settle: float = 3.0):
    """No federation process from a previous run may overlap the next
    measurement (the contamination control from fed_trace_run)."""
    deadline = time.time() + 60.0
    while time.time() < deadline:
        out = subprocess.run(
            ["pgrep", "-f", "fedml_tpu.experiments.distributed_fedavg"],
            capture_output=True, text=True,
        ).stdout.strip()
        if not out:
            break
        time.sleep(1.0)
    time.sleep(settle)


# --- scale mode --------------------------------------------------------------

def run_scale_federation(clients: int, muxers: int, rounds: int,
                         *, seed: int, batch_size: int,
                         round_timeout: float, timeout: float,
                         extra_flags=(), run_dir: str = "",
                         info=None, topology: str = "flat",
                         edge_hubs: int = 0) -> dict:
    """Hub + server + M muxers as OS processes, hub peak RSS recorded.

    A local orchestrator rather than ``launch()``: the hub's pid is
    needed mid-run for the VmHWM read, and at 10k clients the per-
    client stdout plumbing would be pure overhead.

    Reuse hooks (``tools/fed_health_run.py`` drives the FEDHEALTH
    campaign through this function): ``extra_flags`` are appended to
    every role's command line (e.g. ``--stats-plane off``, ``--slo``),
    ``run_dir`` turns on per-process metrics files + the server's
    status/slo artifacts, and ``info`` (a dict) collects the server's
    final stdout JSON (stats-plane stream counts, fault counters).

    ``topology="tree"`` + ``edge_hubs=E`` interposes the hierarchical
    aggregation tier (PR 17): worker units are partitioned contiguously
    into E cohorts, each behind its own ``--role edge_hub`` process,
    and the root hub sees E uplink connections instead of O(clients).
    Each edge's exit stats (partial-fold counters, peak RSS, its local
    hub's churn counters) land in ``info`` as ``edge_<id>_stats``."""
    me = [sys.executable, "-m", "fedml_tpu.experiments.distributed_fedavg"]
    env = _env()
    out_path = os.path.join(tempfile.mkdtemp(prefix="fedscale_"),
                            "final.npz")
    procs = []
    hub = None
    t0 = time.time()
    try:
        hub_flags = ["--run-dir", run_dir] if run_dir else []
        hub = subprocess.Popen(me + ["--role", "hub", "--port", "0"]
                               + hub_flags,
                               stdout=subprocess.PIPE, text=True, env=env)
        port_line = hub.stdout.readline()
        if not port_line:
            raise RuntimeError("hub died before announcing its port")
        port = json.loads(port_line)["hub_port"]
        common = ["--host", "127.0.0.1", "--port", str(port),
                  "--num-clients", str(clients), "--rounds", str(rounds),
                  "--seed", str(seed), "--batch-size", str(batch_size),
                  "--round-timeout", str(round_timeout)]
        common += list(extra_flags)
        if run_dir:
            common += ["--run-dir", run_dir]
        devnull = subprocess.DEVNULL  # 10k digest lines are not evidence here
        units = []
        if muxers:
            base_sz, rem = divmod(clients, muxers)
            start = 1
            for j in range(muxers):
                size = base_sz + (1 if j < rem else 0)
                if size > 0:
                    units.append(("muxer", start, size))
                    start += size
        else:
            units = [("client", i + 1, 1) for i in range(clients)]
        use_tree = topology == "tree" and edge_hubs > 0
        if use_tree:
            # the same contiguous client-count partition launch() uses:
            # whole worker processes (a muxer and its virtual range)
            # are indivisible, so they never straddle an edge boundary
            tree_groups = [[] for _ in range(edge_hubs)]
            acc, gi = 0, 0
            for u in units:
                tree_groups[gi].append(u)
                acc += u[2]
                if (gi < edge_hubs - 1
                        and acc >= (gi + 1) * clients / edge_hubs):
                    gi += 1
            groups = [g for g in tree_groups if g]
        else:
            groups = [units] if units else []
        edge_procs = []
        for group in groups:
            wport = port
            if use_tree:
                first = group[0][1]
                count = sum(u[2] for u in group)
                ep = subprocess.Popen(
                    me + ["--role", "edge_hub", "--node-id", str(first),
                          "--virtual-clients", str(count)] + common,
                    stdout=subprocess.PIPE, text=True, env=env)
                procs.append(ep)
                edge_procs.append(ep)
                line = ep.stdout.readline()
                if not line:
                    raise RuntimeError(
                        "edge hub died before announcing its port")
                wport = json.loads(line)["edge_port"]
            # trailing --port override dials the cohort's own tier
            # (argparse keeps the last occurrence)
            override = [] if wport == port else ["--port", str(wport)]
            for kind, start, size in group:
                if kind == "muxer":
                    procs.append(subprocess.Popen(
                        me + ["--role", "muxer", "--node-id", str(start),
                              "--virtual-clients", str(size)]
                        + common + override, env=env, stdout=devnull))
                else:
                    procs.append(subprocess.Popen(
                        me + ["--role", "client", "--node-id", str(start)]
                        + common + override, env=env, stdout=devnull))
        server = subprocess.Popen(
            me + ["--role", "server", "--out", out_path] + common,
            env=env,
            stdout=subprocess.PIPE if info is not None else None,
            text=True if info is not None else None)
        procs.append(server)
        rc = server.wait(timeout=timeout)
        if info is not None and server.stdout is not None:
            for line in server.stdout.read().splitlines():
                try:
                    info.update(json.loads(line))
                except json.JSONDecodeError:
                    continue
        edge_stats = {}
        for ep in edge_procs:
            # each edge exits on its own after the FINISH drain and
            # prints one stats JSON line (fold counters, peak RSS,
            # local-hub churn) — the tree's per-tier evidence
            try:
                out, _ = ep.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                ep.kill()
                out = None
            for line in (out or "").splitlines():
                try:
                    edge_stats.update(json.loads(line))
                except json.JSONDecodeError:
                    continue
        if info is not None:
            info.update(edge_stats)
        # peak RSS is a high-water mark: reading it AFTER the run (hub
        # still alive) captures the whole federation's pressure
        hub_peak_kb = _vm_kb(hub.pid, "VmHWM")
        wall = round(time.time() - t0, 1)
        rounds_done, walls, finite = _round_walls(out_path)
        if info is not None:
            # graceful hub stop so its shutdown stats line (rebind /
            # shm / drop counters) lands in info too
            hub.terminate()
            try:
                out, _ = hub.communicate(timeout=10)
                for line in (out or "").splitlines():
                    try:
                        info.update(json.loads(line))
                    except json.JSONDecodeError:
                        continue
            except subprocess.TimeoutExpired:
                hub.kill()
        edge_rss = [round(v.get("peak_rss_kb", 0) / 1024.0, 1)
                    for v in edge_stats.values() if isinstance(v, dict)]
        return {
            "clients": clients,
            "muxers": muxers,
            "topology": topology if use_tree else "flat",
            "edge_hubs": len(edge_procs),
            "edge_peak_rss_mb": edge_rss,
            "processes": 2 + (muxers or clients) + len(edge_procs),
            "rc": rc,
            "rounds": rounds_done,
            "nan_free": finite,
            "wall_s": wall,
            "out_path": out_path,
            "hub_peak_rss_mb": round(hub_peak_kb / 1024.0, 1),
            "round_wall_s": {
                "samples": walls,
                "p50": percentile(walls, 0.5),
                "max": max(walls) if walls else None,
            },
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if hub is not None and hub.poll() is None:
            hub.terminate()
            try:
                hub.wait(timeout=10)
            except subprocess.TimeoutExpired:
                hub.kill()


def run_scale(args) -> dict:
    _barrier()
    print(f"== scale reference: {args.ref_clients} per-process clients ==",
          flush=True)
    ref = run_scale_federation(
        args.ref_clients, 0, args.rounds, seed=args.seed,
        batch_size=args.batch_size, round_timeout=args.round_timeout,
        timeout=args.timeout)
    print(json.dumps(ref), flush=True)
    _barrier()
    print(f"== scale run: {args.clients} virtual clients on "
          f"{args.muxers} muxers ==", flush=True)
    big = run_scale_federation(
        args.clients, args.muxers, args.rounds, seed=args.seed,
        batch_size=args.batch_size, round_timeout=args.round_timeout,
        timeout=args.timeout,
        topology=getattr(args, "topology", "flat"),
        edge_hubs=(getattr(args, "edge_hubs", 0)
                   if getattr(args, "topology", "flat") == "tree"
                   else 0))
    print(json.dumps(big), flush=True)
    ratio = (big["hub_peak_rss_mb"] / ref["hub_peak_rss_mb"]
             if ref["hub_peak_rss_mb"] else None)
    return {
        "reference_32proc": ref,
        "scale_run": big,
        "hub_rss_ratio": round(ratio, 2) if ratio is not None else None,
        "thresholds_pre_declared": {"hub_rss_ratio_max": 4.0,
                                    "min_rounds": 3},
        "ok": bool(big["rc"] == 0 and big["nan_free"]
                   and big["rounds"] >= 3
                   and ratio is not None and ratio < 4.0),
    }


# --- churn mode --------------------------------------------------------------

def run_churn(args) -> dict:
    """Connection-churn soak (PR 10's explicit leftover, run over the
    PR 13 transport): every muxer drops + re-helloes its hub connection
    after EVERY trained round and forgets its delta base cache, so each
    round's delta broadcast finds cold rejoiners.  Asserted shape:

    - the federation completes its rounds with a finite model (some
      rounds degrade — a sync can land in a reconnect window; that is
      the deadline's job, not a failure);
    - hub ``node_rebinds`` grows ~muxers x rounds (every re-hello
      rebinds the whole virtual id range);
    - the delta broadcast walks every rejoiner back through the
      full-model path (``comm.delta_full_fallbacks`` resync/no_ack > 0);
    - hub peak RSS stays bounded (churn must not leak connections,
      queues, or slabs).

    Over ``--topology tree`` the rejoin-every-round muxers dial their
    EDGE hub, so the rebind churn lands on the edge tier (counted in
    each ``edge_<id>_stats.local_hub.node_rebinds``) while the root's
    uplink connections stay stable — the tree absorbing connection
    churn at the tier that terminates it is exactly the scaling claim.
    """
    _barrier()
    info: dict = {}
    flags = ["--bcast", "delta", "--rejoin-every-round",
             "--auto-reconnect", "1000", "--shm-min-bytes", "0"]
    if args.lane != "tcp":
        flags += ["--lane", args.lane]
    use_tree = getattr(args, "topology", "flat") == "tree"
    print(f"== churn soak: {args.churn_clients} virtual clients on "
          f"{args.churn_muxers} rejoin-every-round muxers, "
          f"{args.churn_rounds} rounds"
          + (f", {args.edge_hubs} edge hubs" if use_tree else "")
          + " ==", flush=True)
    res = run_scale_federation(
        args.churn_clients, args.churn_muxers, args.churn_rounds,
        seed=args.seed, batch_size=args.batch_size,
        round_timeout=args.churn_round_timeout, timeout=args.timeout,
        extra_flags=flags, info=info,
        topology=getattr(args, "topology", "flat"),
        edge_hubs=getattr(args, "edge_hubs", 0) if use_tree else 0)
    print(json.dumps(res), flush=True)
    hub_stats = info.get("hub_stats") or {}
    faults = info.get("faults") or {}
    fallbacks = {k.split("reason=")[-1].rstrip("}"): v
                 for k, v in faults.items()
                 if k.startswith("comm.delta_full_fallbacks")}
    rebinds = hub_stats.get("node_rebinds", 0)
    if use_tree:
        # the churn terminates at the edge tier: count rebinds there
        rebinds = sum(
            (v.get("local_hub") or {}).get("node_rebinds", 0)
            for k, v in info.items()
            if k.startswith("edge_") and k.endswith("_stats")
            and isinstance(v, dict))
    min_rebinds = args.churn_muxers * max(1, args.churn_rounds - 2)
    return {
        "run": res,
        "lane": args.lane,
        "topology": "tree" if use_tree else "flat",
        "node_rebinds": rebinds,
        "delta_full_fallbacks": fallbacks,
        "hub_stats": hub_stats,
        "server_counters": faults,
        "thresholds_pre_declared": {
            "min_node_rebinds": min_rebinds,
            "full_fallbacks_required": True,
            "hub_rss_mb_max": 256.0,
        },
        "ok": bool(res["rc"] == 0 and res["nan_free"]
                   and rebinds >= min_rebinds
                   and sum(fallbacks.values()) > 0
                   and res["hub_peak_rss_mb"] < 256.0),
    }


# --- ab mode -----------------------------------------------------------------

def run_ab(args) -> dict:
    from fedml_tpu.experiments.distributed_fedavg import launch

    env = _env()

    def one(tag: str, clients: int, muxers: int, hotpath: str) -> dict:
        _barrier()
        out = os.path.join(tempfile.mkdtemp(prefix=f"fedab_{tag}_"),
                           "final.npz")
        t0 = time.time()
        rc = launch(
            num_clients=clients, rounds=args.ab_rounds, seed=args.seed,
            batch_size=args.batch_size, out_path=out,
            round_timeout=args.round_timeout,
            codec="none", wire=2, input_dim=args.input_dim,
            hotpath=hotpath, train_samples=args.train_samples,
            muxers=muxers, env=env,
            timeout=600.0 + args.ab_rounds * args.round_timeout,
        )
        if rc != 0:
            raise SystemExit(f"{tag}: federation failed rc={rc}")
        rounds_done, walls, finite = _round_walls(out)
        rec = {"tag": tag, "clients": clients, "muxers": muxers,
               "hotpath": hotpath, "rounds": rounds_done,
               "nan_free": finite,
               "wall_s": round(time.time() - t0, 1),
               "round_wall_s": {"samples": walls,
                                "p50": percentile(walls, 0.5),
                                "p95": percentile(walls, 0.95)}}
        print(json.dumps(rec), flush=True)
        return rec

    arms = {"mux": ("mux", 1, "fast"),
            "proc_fast": ("proc_fast", 0, "fast"),
            "proc_legacy": ("proc_legacy", 0, "legacy")}
    reps = {k: [] for k in arms}
    # ABBA interleave (PR-6 protocol): adjacent pairs share box state,
    # so linear drift cancels instead of loading onto one arm
    for i in range(args.reps):
        order = list(arms) if i % 2 == 0 else list(arms)[::-1]
        for k in order:
            tag, muxers, hotpath = arms[k]
            reps[k].append(one(f"{tag}_r{i}", args.ab_clients,
                               muxers, hotpath))

    def pooled(rs):
        samples = [s for r in rs for s in r["round_wall_s"]["samples"]]
        return {"reps": len(rs),
                "per_rep_p50": [r["round_wall_s"]["p50"] for r in rs],
                "per_rep_wall_s": [r["wall_s"] for r in rs],
                "p50_pooled": percentile(samples, 0.5),
                "p95_pooled": percentile(samples, 0.95),
                "samples": samples}

    out = {k: pooled(v) for k, v in reps.items()}
    # verdict estimator: median of per-rep p50s (robust to one run
    # caught in the box's slow scheduling mode — fed_trace_run doc)
    p50 = {k: percentile(v["per_rep_p50"], 0.5) for k, v in out.items()}
    big = one(f"mux_{args.big_clients}", args.big_clients, args.big_muxers,
              "fast")
    return {
        "config": {
            "input_dim": args.input_dim,
            "model_mb": round((args.input_dim * 2 + 2) * 4 / 1e6, 2),
            "train_samples": args.train_samples,
            "rounds": args.ab_rounds,
            "reps": args.reps,
            "protocol": "ABBA interleaved, process barrier + settle, "
                        "verdict = median of per-rep p50s (PR-6)",
        },
        "arms_32": out,
        "p50_by_arm": p50,
        "big_muxed_datapoint": big,
        "thresholds_pre_declared": {
            "mux_p50_max": "<= proc_legacy p50 (close the FEDLAT_r09 "
                           "+14% gap)",
        },
        "verdict": {
            "mux_p50": p50.get("mux"),
            "proc_fast_p50": p50.get("proc_fast"),
            "proc_legacy_p50": p50.get("proc_legacy"),
            "mux_vs_legacy": (round(p50["mux"] / p50["proc_legacy"], 3)
                              if p50.get("proc_legacy") else None),
            "mux_vs_fast": (round(p50["mux"] / p50["proc_fast"], 3)
                            if p50.get("proc_fast") else None),
            "ok": bool(p50.get("mux") is not None
                       and p50.get("proc_legacy") is not None
                       and p50["mux"] <= p50["proc_legacy"]),
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mode", choices=["scale", "ab", "both", "churn"],
                   default="both")
    p.add_argument("--out", default="FEDSCALE_r10.json")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--batch-size", type=int, default=16)
    # scale knobs
    p.add_argument("--clients", type=int, default=10000)
    p.add_argument("--muxers", type=int, default=4)
    p.add_argument("--ref-clients", type=int, default=32)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--round-timeout", type=float, default=600.0)
    p.add_argument("--timeout", type=float, default=3600.0)
    # ab knobs (FEDLAT_r09 regime)
    p.add_argument("--ab-clients", type=int, default=32)
    p.add_argument("--ab-rounds", type=int, default=7)
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--input-dim", type=int, default=131072)
    p.add_argument("--train-samples", type=int, default=16)
    p.add_argument("--big-clients", type=int, default=256)
    p.add_argument("--big-muxers", type=int, default=1)
    # churn knobs (PR 13; PR 17 raises the default to "high virtual
    # counts" — the PR-10 leftover — and adds the tree topology):
    # muxers re-hello every round over --lane
    p.add_argument("--lane", choices=["tcp", "shm"], default="shm")
    p.add_argument("--churn-clients", type=int, default=512)
    p.add_argument("--churn-muxers", type=int, default=2)
    p.add_argument("--churn-rounds", type=int, default=5)
    p.add_argument("--churn-round-timeout", type=float, default=60.0)
    # topology knobs (PR 17): run scale/churn over the hierarchical
    # aggregation tree — worker cohorts behind --edge-hubs edge tiers
    p.add_argument("--topology", choices=["flat", "tree"],
                   default="flat")
    p.add_argument("--edge-hubs", type=int, default=2)
    args = p.parse_args(argv)

    artifact = {
        "experiment": (
            "virtual-client multiplexing (hello v2 + muxer role + "
            "vmapped cohort engine): 10k-client scale proof with "
            "bounded hub RSS, and the FEDLAT-style muxed-vs-per-"
            "process latency A/B at 32 virtual clients"
        ),
        "generated_unix": round(time.time(), 1),
    }
    ok = True
    if args.mode in ("scale", "both"):
        artifact["scale"] = run_scale(args)
        ok = ok and artifact["scale"]["ok"]
    if args.mode in ("ab", "both"):
        artifact["latency_ab"] = run_ab(args)
        ok = ok and artifact["latency_ab"]["verdict"]["ok"]
    if args.mode == "churn":
        artifact["churn"] = run_churn(args)
        ok = ok and artifact["churn"]["ok"]
    with open(args.out, "w") as fh:
        json.dump(artifact, fh, indent=1, default=float)
    print(json.dumps({"out": args.out, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
