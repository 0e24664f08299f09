#!/usr/bin/env python
"""Reactor-hub evidence run → ``FEDHUB_r20.json``.

A/B campaign over the PR-20 data plane — the selector-driven reactor
hub (``comm/tcp.py`` mode="reactor") against the retained threaded
plane — with every bar pre-declared:

**pins** — the byte-identity matrix: {fp32, int8+EF} x {tcp, shm} x
{full, delta} x {muxed, per-process}, each cell run ONCE per plane at
the same seed; the per-client sha256 upload digests must be identical
reactor-vs-threaded in all 16 cells (the reactor is a pure scheduling
change — same frames, same bytes, different thread inventory).

**threads** — the O(1)-threads claim, measured from /proc: a hub
subprocess under 512 raw dialer connections must hold ≤ 8 OS threads
(the threaded plane holds ~1 + senders + 2/conn ≈ 1040 at that point,
measured here at 32 conns where it is ~70).

**churn** — 512-conn accept/churn soak vs the threaded plane at 32:
reactor hub RSS and churn-wave accept p50 must stay ≤ 1.1x the
threaded-at-32 baseline (the reactor may not buy its fd scale with
per-conn memory or accept-path latency).

**round_wall** — end-to-end p50 round wall, 32 per-process clients in
the FEDLAT comm-dominant regime, ABBA-interleaved reps, verdict =
median of per-rep p50s (PR-6 protocol): reactor ≤ 1.05x threaded.

**zero_copy** — on the laned path (shm ring + muxer) the reactor hub
must report ``shm_hub_copies == 0`` with ``zero_copy_forwards > 0``:
inbound payloads stay pinned slab/pool regions end to end, released at
drain, never materialized.

**chaos** — summarized from the separate 17-scenario soak artifact:
``python tools/chaos_run.py --matrix default --out FAULTS_r20.json``
(run it first; this tool folds its verdict in by reference).

Usage:
    python tools/fed_hub_run.py --mode all --out FEDHUB_r20.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.trace_summary import percentile  # noqa: E402

ENV_HUB_MODE = "FEDML_TPU_HUB_MODE"


def _env(mode: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env[ENV_HUB_MODE] = mode
    return env


def _barrier(settle: float = 2.0):
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


def _round_walls(npz_path: str):
    import numpy as np

    z = np.load(npz_path)
    log = json.loads(str(z["round_log"]))
    stamps = [r["t"] for r in log if isinstance(r.get("t"), (int, float))]
    deltas = [round(b - a, 4) for a, b in zip(stamps, stamps[1:])]
    finite = all(
        bool(np.isfinite(z[k]).all())
        for k in z.files if k.startswith("leaf_")
    )
    return int(z["rounds"]), deltas, finite


def _digests(info):
    return {k: v for k, v in sorted(info.items())
            if k.endswith("_upload_digest")}


def _one(tag, mode, *, clients, rounds, seed, input_dim, train_samples,
         lane="tcp", bcast="full", codec="none", muxers=0,
         timeout=900.0, round_timeout=600.0):
    from fedml_tpu.experiments.distributed_fedavg import launch

    _barrier()
    out = os.path.join(tempfile.mkdtemp(prefix=f"fedhub_{tag}_"),
                       "final.npz")
    info: dict = {}
    t0 = time.time()
    rc = launch(
        num_clients=clients, rounds=rounds, seed=seed, batch_size=16,
        out_path=out, env=_env(mode), info=info,
        timeout=timeout, round_timeout=round_timeout,
        input_dim=input_dim, train_samples=train_samples,
        lane=lane, bcast=bcast, codec=codec, muxers=muxers,
    )
    if rc != 0:
        raise SystemExit(f"{tag}: federation failed rc={rc}")
    rounds_done, walls, finite = _round_walls(out)
    hub = info.get("hub_stats") or {}
    rec = {
        "tag": tag, "mode": mode, "clients": clients, "muxers": muxers,
        "lane": lane, "bcast": bcast, "codec": codec,
        "rounds": rounds_done, "nan_free": finite,
        "wall_s": round(time.time() - t0, 1),
        "round_wall_s": {"samples": walls,
                         "p50": percentile(walls, 0.5),
                         "p95": percentile(walls, 0.95)},
        "hub": {k: hub.get(k) for k in
                ("mode", "threads", "open_fds", "shm_frames",
                 "shm_hub_copies", "zero_copy_forwards") if k in hub},
        "digests": _digests(info),
    }
    print(json.dumps({k: rec[k] for k in
                      ("tag", "mode", "rounds", "nan_free", "wall_s")}),
          flush=True)
    return rec


# ---- pins: 16-cell reactor-vs-threaded byte identity ------------------------

def run_pins(args) -> dict:
    cells = {}
    ok = True
    for codec_tag, codec in (("fp32", "none"), ("int8ef", "int8")):
        for lane in ("tcp", "shm"):
            for bcast in ("full", "delta"):
                for topo_tag, muxers in (("mux", 1), ("proc", 0)):
                    cell = f"{codec_tag}|{lane}|{bcast}|{topo_tag}"
                    digs = {}
                    for mode in ("reactor", "threaded"):
                        rec = _one(
                            f"pin_{codec_tag}_{lane}_{bcast}_"
                            f"{topo_tag}_{mode}",
                            mode, clients=args.pin_clients,
                            rounds=args.pin_rounds, seed=args.seed,
                            input_dim=args.pin_input_dim,
                            train_samples=30, lane=lane, bcast=bcast,
                            codec=codec, muxers=muxers)
                        digs[mode] = rec["digests"]
                    same = (digs["reactor"] == digs["threaded"]
                            and bool(digs["reactor"]))
                    cells[cell] = {
                        "identical": same,
                        "n_digests": len(digs["reactor"]),
                    }
                    ok = ok and same
    return {
        "config": {"clients": args.pin_clients,
                   "rounds": args.pin_rounds,
                   "input_dim": args.pin_input_dim, "seed": args.seed,
                   "protocol": "one run per plane per cell, same seed; "
                               "per-client sha256 upload digests must "
                               "match exactly"},
        "cells": cells,
        "ok": ok,
    }


# ---- threads / churn: raw-dialer soak against a hub subprocess --------------

def _proc_status(pid: int):
    with open(f"/proc/{pid}/status") as fh:
        txt = fh.read()
    threads = int(re.search(r"Threads:\s*(\d+)", txt).group(1))
    rss_kb = int(re.search(r"VmRSS:\s*(\d+)", txt).group(1))
    return threads, rss_kb


def _spawn_hub(mode: str):
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "fedml_tpu.experiments.distributed_fedavg",
         "--role", "hub", "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_env(mode))
    line = proc.stdout.readline()
    if not line:
        raise SystemExit(f"{mode} hub died before announcing its port")
    return proc, json.loads(line)["hub_port"]


def _dial(port: int, node_id: int, timeout=15.0) -> float:
    """Hand-rolled hello-v1 dialer; returns connect->ACK latency."""
    t0 = time.perf_counter()
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    f = s.makefile("rb")
    s.sendall((json.dumps({"node_id": node_id}) + "\n").encode())
    ack = json.loads(f.readline())
    assert ack.get("__hub__") == "ack"
    lat = time.perf_counter() - t0
    s.sendall((json.dumps({"__hub__": "ping_done"}) + "\n").encode())
    f.close()
    return lat, s


def _soak_arm(mode: str, conns: int, churn_waves: int) -> dict:
    proc, port = _spawn_hub(mode)
    socks = {}
    try:
        fill_lat = []
        for i in range(conns):
            lat, s = _dial(port, 1000 + i)
            fill_lat.append(lat)
            socks[i] = s
        time.sleep(1.0)  # let registration settle before sampling
        threads, rss_kb = _proc_status(proc.pid)
        churn_lat = []
        wave = max(1, conns // 4)
        for w in range(churn_waves):
            for i in range(wave):
                socks.pop(i).close()
            time.sleep(0.5)
            for i in range(wave):
                lat, s = _dial(port, 1000 + i)
                churn_lat.append(lat)
                socks[i] = s
        threads2, rss2_kb = _proc_status(proc.pid)
        return {
            "mode": mode, "conns": conns, "churn_waves": churn_waves,
            "threads": max(threads, threads2),
            "rss_mb": round(max(rss_kb, rss2_kb) / 1024, 1),
            "accept_p50_s": percentile(sorted(fill_lat), 0.5),
            "churn_accept_p50_s": (percentile(sorted(churn_lat), 0.5)
                                   if churn_lat else None),
        }
    finally:
        for s in socks.values():
            try:
                s.close()
            except OSError:
                pass
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def run_soak(args) -> dict:
    reactor = _soak_arm("reactor", args.soak_conns, churn_waves=3)
    threaded = _soak_arm("threaded", 32, churn_waves=3)
    rss_ratio = (reactor["rss_mb"] / threaded["rss_mb"]
                 if threaded["rss_mb"] else None)
    accept_ratio = (
        reactor["churn_accept_p50_s"] / threaded["churn_accept_p50_s"]
        if threaded.get("churn_accept_p50_s") else None)
    threads_section = {
        "reactor_threads_512": reactor["threads"],
        "threaded_threads_32": threaded["threads"],
        "bar": "reactor process <= 8 OS threads at 512 conns",
        "ok": reactor["threads"] <= 8,
    }
    churn_section = {
        "reactor": reactor,
        "threaded_32": threaded,
        "rss_ratio": round(rss_ratio, 3) if rss_ratio else None,
        "accept_ratio": (round(accept_ratio, 3)
                         if accept_ratio else None),
        "thresholds_pre_declared": {
            "rss_ratio_max": 1.1,
            "accept_ratio_max": 1.1,
        },
        "ok": bool(rss_ratio is not None and rss_ratio <= 1.1
                   and accept_ratio is not None and accept_ratio <= 1.1),
    }
    return {"threads": threads_section, "churn": churn_section}


# ---- round wall: end-to-end ABBA A/B ----------------------------------------

def run_round_wall(args) -> dict:
    arms = {"reactor": [], "threaded": []}
    for i in range(args.reps):
        order = list(arms) if i % 2 == 0 else list(arms)[::-1]
        for mode in order:
            arms[mode].append(_one(
                f"p50_{mode}_r{i}", mode, clients=args.ab_clients,
                rounds=args.ab_rounds, seed=args.seed,
                input_dim=args.input_dim,
                train_samples=args.train_samples,
                timeout=args.timeout))
    p50 = {k: percentile([r["round_wall_s"]["p50"] for r in v], 0.5)
           for k, v in arms.items()}
    ratio = (p50["reactor"] / p50["threaded"]
             if p50.get("threaded") else None)
    return {
        "config": {"clients": args.ab_clients, "rounds": args.ab_rounds,
                   "input_dim": args.input_dim,
                   "train_samples": args.train_samples,
                   "reps": args.reps,
                   "protocol": "ABBA interleaved, process barrier + "
                               "settle, verdict = median of per-rep "
                               "p50s (PR-6)"},
        "arms": arms,
        "p50_by_arm": p50,
        "ratio": round(ratio, 3) if ratio else None,
        "thresholds_pre_declared": {"ratio_max": 1.05},
        "ok": bool(ratio is not None and ratio <= 1.05),
    }


# ---- zero copy: laned path, reactor -----------------------------------------

def run_zero_copy(args) -> dict:
    rec = _one("zcopy_shm_mux", "reactor", clients=8, rounds=3,
               seed=args.seed, input_dim=65536, train_samples=16,
               lane="shm", muxers=1)
    hub = rec["hub"]
    copies = hub.get("shm_hub_copies", -1)
    fwds = hub.get("zero_copy_forwards", 0)
    return {
        "run": {k: rec[k] for k in ("tag", "rounds", "nan_free")},
        "hub": hub,
        "shm_hub_copies": copies,
        "zero_copy_forwards": fwds,
        "thresholds_pre_declared": {
            "shm_hub_copies": 0,
            "zero_copy_forwards_min": 1,
        },
        "ok": bool(copies == 0 and fwds > 0),
    }


# ---- chaos: fold the separate FAULTS artifact in by reference ---------------

def run_chaos(args) -> dict:
    try:
        with open(args.faults) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        return {"artifact": args.faults, "ok": False,
                "note": f"unreadable ({type(e).__name__}) — run "
                        f"tools/chaos_run.py --matrix default first"}
    scenarios = doc.get("scenarios") or []
    survived = sum(1 for s in scenarios if s.get("survived"))
    return {
        "artifact": args.faults,
        "scenarios": len(scenarios),
        "survived": survived,
        "all_nan_free": bool(doc.get("all_nan_free")),
        "ok": bool(doc.get("all_nan_free") and len(scenarios) >= 17
                   and survived == len(scenarios)),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mode",
                   choices=["pins", "soak", "round_wall", "zero_copy",
                            "chaos", "all"],
                   default="all")
    p.add_argument("--out", default="FEDHUB_r20.json")
    p.add_argument("--faults", default="FAULTS_r20.json")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--ab-clients", type=int, default=32)
    p.add_argument("--ab-rounds", type=int, default=5)
    p.add_argument("--input-dim", type=int, default=131072)
    p.add_argument("--train-samples", type=int, default=16)
    p.add_argument("--pin-clients", type=int, default=4)
    p.add_argument("--pin-rounds", type=int, default=3)
    p.add_argument("--pin-input-dim", type=int, default=4096)
    p.add_argument("--soak-conns", type=int, default=512)
    p.add_argument("--timeout", type=float, default=900.0,
                   help="per-federation launch timeout for the A/B "
                        "round-wall arms (32 comm-heavy processes on "
                        "an oversubscribed box need headroom)")
    args = p.parse_args(argv)

    artifact = {}
    if os.path.exists(args.out):
        # partial re-runs MERGE into the existing artifact
        try:
            with open(args.out) as fh:
                artifact = json.load(fh)
        except (OSError, json.JSONDecodeError):
            artifact = {}
    artifact["experiment"] = (
        "reactor hub data plane: one selectors event-loop thread "
        "multiplexes every hub connection (streaming frame parser, "
        "bounded send queues, writability-driven drain) with "
        "end-to-end zero-copy routing (refcounted slab/pool pins, "
        "released at drain) — vs the retained threaded plane"
    )
    artifact["generated_unix"] = round(time.time(), 1)

    def _save():
        # verdict spans every section measured so far (this run or a
        # prior partial one), and the artifact lands on disk after EACH
        # section — a multi-hour campaign that dies mid-section keeps
        # everything already measured
        oks = [artifact[k].get("ok") for k in
               ("pins", "threads", "churn", "round_wall", "zero_copy",
                "chaos") if k in artifact]
        artifact["ok"] = bool(oks) and all(bool(o) for o in oks)
        with open(args.out, "w") as fh:
            json.dump(artifact, fh, indent=1, default=float)

    if args.mode in ("pins", "all"):
        artifact["pins"] = run_pins(args)
        _save()
    if args.mode in ("soak", "all"):
        soak = run_soak(args)
        artifact["threads"] = soak["threads"]
        artifact["churn"] = soak["churn"]
        _save()
    if args.mode in ("round_wall", "all"):
        artifact["round_wall"] = run_round_wall(args)
        _save()
    if args.mode in ("zero_copy", "all"):
        artifact["zero_copy"] = run_zero_copy(args)
        _save()
    if args.mode in ("chaos", "all"):
        artifact["chaos"] = run_chaos(args)
        _save()
    print(json.dumps({"out": args.out, "ok": artifact["ok"]}))
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
