#!/usr/bin/env python
"""Reactor-hub evidence run → a ``FEDHUB_`` artifact.

Campaign over the hub's event-loop data plane (``comm/tcp.py``), with
every bar pre-declared:

**threads** — the O(1)-threads claim, measured from /proc: a hub
subprocess under 512 raw dialer connections must hold ≤ 8 OS threads.
The same soak records the hub's RSS and its accept p50 through three
churn waves (``churn``; recorded, no bar).

**zero_copy** — on the laned path (shm ring + muxer) the reactor hub
must report ``shm_hub_copies == 0`` with ``zero_copy_forwards > 0``:
inbound payloads stay pinned slab/pool regions end to end, released at
drain, never materialized.

**chaos** — summarized from the separate 17-scenario soak artifact:
``python tools/chaos_run.py --matrix default --out FAULTS_r20.json``
(run it first; this tool folds its verdict in by reference).

Usage:
    python tools/fed_hub_run.py --mode all --out FEDHUB_rNN.json

``FEDHUB_r20.json`` is the record of the PR-20 campaign, which also
compared the reactor against the threaded plane it retired; ``--out``
has no default so a new run never merges into it.
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


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
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


def _rounds_and_finite(npz_path: str):
    import numpy as np

    z = np.load(npz_path)
    finite = all(
        bool(np.isfinite(z[k]).all())
        for k in z.files if k.startswith("leaf_")
    )
    return int(z["rounds"]), finite


def _one(tag, *, clients, rounds, seed, input_dim, train_samples,
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
        out_path=out, env=_env(), info=info,
        timeout=timeout, round_timeout=round_timeout,
        input_dim=input_dim, train_samples=train_samples,
        lane=lane, bcast=bcast, codec=codec, muxers=muxers,
    )
    if rc != 0:
        raise SystemExit(f"{tag}: federation failed rc={rc}")
    rounds_done, finite = _rounds_and_finite(out)
    hub = info.get("hub_stats") or {}
    rec = {
        "tag": tag, "clients": clients, "muxers": muxers,
        "lane": lane, "bcast": bcast, "codec": codec,
        "rounds": rounds_done, "nan_free": finite,
        "wall_s": round(time.time() - t0, 1),
        "hub": {k: hub.get(k) for k in
                ("threads", "open_fds", "shm_frames",
                 "shm_hub_copies", "zero_copy_forwards") if k in hub},
    }
    print(json.dumps({k: rec[k] for k in
                      ("tag", "rounds", "nan_free", "wall_s")}),
          flush=True)
    return rec


# ---- threads / churn: raw-dialer soak against a hub subprocess --------------

def _proc_status(pid: int):
    with open(f"/proc/{pid}/status") as fh:
        txt = fh.read()
    threads = int(re.search(r"Threads:\s*(\d+)", txt).group(1))
    rss_kb = int(re.search(r"VmRSS:\s*(\d+)", txt).group(1))
    return threads, rss_kb


def _spawn_hub():
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "fedml_tpu.experiments.distributed_fedavg",
         "--role", "hub", "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=_env())
    line = proc.stdout.readline()
    if not line:
        raise SystemExit("hub died before announcing its port")
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


def _soak_arm(conns: int, churn_waves: int) -> dict:
    proc, port = _spawn_hub()
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
            "conns": conns, "churn_waves": churn_waves,
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
    arm = _soak_arm(args.soak_conns, churn_waves=3)
    threads_section = {
        "reactor_threads_512": arm["threads"],
        "bar": "hub process <= 8 OS threads at 512 conns",
        "ok": arm["threads"] <= 8,
    }
    return {"threads": threads_section, "churn": {"reactor": arm}}


# ---- zero copy: laned path, reactor -----------------------------------------

def run_zero_copy(args) -> dict:
    rec = _one("zcopy_shm_mux", clients=8, rounds=3,
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
                   choices=["soak", "zero_copy", "chaos", "all"],
                   default="all")
    p.add_argument("--out", required=True)
    p.add_argument("--faults", default="FAULTS_r20.json")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--soak-conns", type=int, default=512)
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
        "released at drain)"
    )
    artifact["generated_unix"] = round(time.time(), 1)

    def _save():
        # verdict spans every section measured so far (this run or a
        # prior partial one), and the artifact lands on disk after EACH
        # section — a multi-hour campaign that dies mid-section keeps
        # everything already measured
        oks = [artifact[k].get("ok") for k in
               ("threads", "zero_copy", "chaos") if k in artifact]
        artifact["ok"] = bool(oks) and all(bool(o) for o in oks)
        with open(args.out, "w") as fh:
            json.dump(artifact, fh, indent=1, default=float)

    if args.mode in ("soak", "all"):
        soak = run_soak(args)
        artifact["threads"] = soak["threads"]
        artifact["churn"] = soak["churn"]
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
