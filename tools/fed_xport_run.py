#!/usr/bin/env python
"""Raw-speed transport evidence run → ``FEDXPORT_r13.json``.

A/B campaign over the PR-13 levers — the shared-memory lane
(``comm/shm.py``) and the delta broadcast (``fedavg_cross_device
--bcast delta``) — with every bar pre-declared:

**ab32** — {tcp, shm} x {full, delta} at 32 per-process clients in the
FEDLAT regime (``--input-dim 131072`` ≈ 1.05 MB model,
``--train-samples 16`` comm-dominant), ABBA-interleaved reps, verdict =
median of per-rep p50s (the PR-6 protocol).  Bytes evidence from the
server's exact wire counters: the delta arm's steady-state broadcast
bytes/round must be ≥ 3x smaller than the full arm's per-round sync
payload.  The same-seed tcp-vs-shm arms double as the lane's digest
pin: per-client upload digests and byte accounting must be identical
(the lane is payload-transparent).

**big256** — the FEDSCALE_r10 hot point: 256 virtual clients on ONE
muxer, 269 MB of uploads/round through one connection — {tcp, shm}
ABBA.  Pre-declared: shm p50 round wall ≤ tcp (target ≥ 1.3x faster).

**digests** — delta-vs-full byte identity at the same chain codec
(delta is a pure wire change), plus shm-vs-delta composition.

The chaos soak over the new path is a separate artifact:
``python tools/chaos_run.py --lane shm --bcast delta --out
FAULTS_r13.json`` (11 scenarios incl. shm_ring_full/shm_peer_crash).

Usage:
    python tools/fed_xport_run.py --mode all --out FEDXPORT_r13.json
"""

from __future__ import annotations

import argparse
import json
import os
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


def _barrier(settle: float = 3.0):
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


def _one(tag, *, clients, rounds, seed, input_dim, train_samples,
         lane, bcast, muxers=0, bcast_codec="", timeout=900.0,
         round_timeout=600.0, collect_info=True):
    from fedml_tpu.experiments.distributed_fedavg import launch

    _barrier()
    out = os.path.join(tempfile.mkdtemp(prefix=f"fedxport_{tag}_"),
                       "final.npz")
    info: dict = {}
    t0 = time.time()
    rc = launch(
        num_clients=clients, rounds=rounds, seed=seed, batch_size=16,
        out_path=out, env=_env(),
        info=info if collect_info else None,
        timeout=timeout, round_timeout=round_timeout,
        input_dim=input_dim, train_samples=train_samples,
        lane=lane, bcast=bcast, bcast_codec=bcast_codec, muxers=muxers,
    )
    if rc != 0:
        raise SystemExit(f"{tag}: federation failed rc={rc}")
    rounds_done, walls, finite = _round_walls(out)
    comm = info.get("comm_bytes") or {}
    faults = info.get("faults") or {}
    hub = info.get("hub_stats") or {}
    rec = {
        "tag": tag, "clients": clients, "muxers": muxers,
        "lane": lane, "bcast": bcast, "rounds": rounds_done,
        "nan_free": finite, "wall_s": round(time.time() - t0, 1),
        "round_wall_s": {"samples": walls,
                         "p50": percentile(walls, 0.5),
                         "p95": percentile(walls, 0.95)},
        "sync_sent_bytes": comm.get(
            "comm.sent_bytes{msg_type=S2C_SYNC_MODEL}", 0),
        "init_sent_bytes": comm.get(
            "comm.sent_bytes{msg_type=S2C_INIT_CONFIG}", 0),
        "delta_bcast_bytes": faults.get("comm.delta_bcast_bytes", 0),
        "delta_full_fallbacks": {
            k: v for k, v in faults.items()
            if k.startswith("comm.delta_full_fallbacks")},
        "shm_counters": {k: v for k, v in faults.items()
                         if k.startswith("comm.shm_")},
        "hub_shm": {k: hub.get(k) for k in
                    ("shm_conns", "shm_frames", "shm_bytes",
                     "shm_fallbacks") if k in hub},
        "digests": _digests(info),
    }
    print(json.dumps({k: rec[k] for k in
                      ("tag", "rounds", "nan_free", "wall_s",
                       "round_wall_s")}), flush=True)
    return rec


def run_ab32(args) -> dict:
    arms = {
        "tcp_full": ("tcp", "full"),
        "shm_full": ("shm", "full"),
        "tcp_delta": ("tcp", "delta"),
        "shm_delta": ("shm", "delta"),
    }
    reps = {k: [] for k in arms}
    for i in range(args.reps):
        order = list(arms) if i % 2 == 0 else list(arms)[::-1]
        for k in order:
            lane, bcast = arms[k]
            reps[k].append(_one(
                f"{k}_r{i}", clients=args.ab_clients,
                rounds=args.ab_rounds, seed=args.seed,
                input_dim=args.input_dim,
                train_samples=args.train_samples, lane=lane, bcast=bcast))
    p50 = {k: percentile([r["round_wall_s"]["p50"] for r in v], 0.5)
           for k, v in reps.items()}
    # bytes: full arm = per-round sync payload; delta arm = the encoded
    # chain updates actually shipped, steady-state (rounds after the
    # full INIT round — the counter only counts delta groups)
    full0 = reps["tcp_full"][0]
    delta0 = reps["tcp_delta"][0]
    full_per_round = full0["sync_sent_bytes"] / max(1, full0["rounds"] - 1)
    delta_per_round = (delta0["delta_bcast_bytes"]
                       / max(1, delta0["rounds"] - 1))
    bytes_ratio = (full_per_round / delta_per_round
                   if delta_per_round else None)
    # lane digest pin: same-seed tcp-vs-shm at the same bcast mode
    digest_pin = {
        "full": (full0["digests"] == reps["shm_full"][0]["digests"]
                 and bool(full0["digests"])),
        "delta": (delta0["digests"] == reps["shm_delta"][0]["digests"]
                  and bool(delta0["digests"])),
    }
    shm_moved = reps["shm_full"][0]["hub_shm"].get("shm_bytes", 0)
    return {
        "config": {"clients": args.ab_clients, "rounds": args.ab_rounds,
                   "input_dim": args.input_dim,
                   "model_mb": round((args.input_dim * 2 + 2) * 4 / 1e6, 2),
                   "train_samples": args.train_samples, "reps": args.reps,
                   "protocol": "ABBA interleaved, process barrier + "
                               "settle, verdict = median of per-rep "
                               "p50s (PR-6)"},
        "arms": reps,
        "p50_by_arm": p50,
        "bcast_bytes_per_round": {"full": full_per_round,
                                  "delta_steady_state": delta_per_round,
                                  "ratio": (round(bytes_ratio, 2)
                                            if bytes_ratio else None)},
        "shm_vs_tcp_digest_identical": digest_pin,
        "hub_shm_bytes_shm_full_rep0": shm_moved,
        "thresholds_pre_declared": {
            "delta_bytes_ratio_min": 3.0,
            "digest_pins": "tcp==shm per-client upload digests, both "
                           "bcast modes",
        },
        "ok": bool(bytes_ratio is not None and bytes_ratio >= 3.0
                   and all(digest_pin.values())),
    }


def run_big256(args) -> dict:
    arms = {"tcp": "tcp", "shm": "shm"}
    reps = {k: [] for k in arms}
    for i in range(args.big_reps):
        order = list(arms) if i % 2 == 0 else list(arms)[::-1]
        for k in order:
            reps[k].append(_one(
                f"big_{k}_r{i}", clients=args.big_clients,
                rounds=args.big_rounds, seed=args.seed,
                input_dim=args.input_dim,
                train_samples=args.train_samples, lane=arms[k],
                bcast="full", muxers=1, timeout=1800.0,
                collect_info=True))

    def rep_p50(r):
        # the FIRST inter-round gap carries the 256-cohort vmap jit
        # compile (one-time, many seconds on this box) — a warmup
        # artifact, not transport: excluded when later gaps exist
        walls = r["round_wall_s"]["samples"]
        steady = walls[1:] if len(walls) > 1 else walls
        return percentile(steady, 0.5)

    p50 = {k: percentile([rep_p50(r) for r in v], 0.5)
           for k, v in reps.items()}
    speedup = (p50["tcp"] / p50["shm"]
               if p50.get("shm") and p50.get("tcp") else None)
    upload_mb = round(args.big_clients * (args.input_dim * 2 + 2) * 4
                      / 1e6, 1)
    return {
        "config": {"virtual_clients": args.big_clients, "muxers": 1,
                   "rounds": args.big_rounds,
                   "uploads_per_round_mb": upload_mb,
                   "reps": args.big_reps,
                   "p50_protocol": "per-rep p50 over steady-state "
                                   "inter-round gaps (first gap = cohort "
                                   "jit warmup, excluded), verdict = "
                                   "median of rep p50s"},
        "arms": reps,
        "p50_by_arm": p50,
        "shm_speedup": round(speedup, 3) if speedup else None,
        "thresholds_pre_declared": {
            "shm_p50_max": "<= tcp p50 (hard)",
            "shm_speedup_target": 1.3,
        },
        "ok": bool(speedup is not None and speedup >= 1.0),
    }


def run_micro(args) -> dict:
    """Quiet-box per-frame transport micro-benchmark (the PR-6 style
    mechanism probe): one sender → hub → one receiver, 1.05 MB frames,
    tcp vs shm, in-process.  Isolates the raw lane mechanism from the
    federation's compute/codec costs — at the 256-virtual point the
    round wall is dominated by the vmapped train step + upload
    encode/digest + server decode/fold, so the end-to-end A/B above
    bounds the lane's effect while THIS number shows the mechanism."""
    import numpy as np

    from fedml_tpu.comm.message import Message
    from fedml_tpu.comm.tcp import TcpBackend, TcpHub

    def arm(lane: str, frames: int = 64) -> float:
        kw = ({"lane": "shm", "shm_min_bytes": 0} if lane == "shm"
              else {})
        hub = TcpHub(shm_min_bytes=0)
        got = []

        class Obs:
            def receive_message(self, t, m):
                # force-touch the payload (a real consumer decodes it)
                got.append(float(np.asarray(m.get("x"))[-1]))

        rx = tx = None
        try:
            rx = TcpBackend(1, hub.host, hub.port, **kw)
            rx.add_observer(Obs())
            rx.run_in_thread()
            tx = TcpBackend(9, hub.host, hub.port, **kw)
            tx.await_peers([1])
            payload = np.arange(262144, dtype=np.float32)
            for i in range(3):  # warmup
                m = Message("MICRO", 9, 1)
                m.add_params("x", payload)
                tx.send_message(m)
            deadline = time.time() + 30
            while len(got) < 3 and time.time() < deadline:
                time.sleep(0.005)
            t0 = time.perf_counter()
            for i in range(frames):
                m = Message("MICRO", 9, 1)
                m.add_params("x", payload)
                tx.send_message(m)
            deadline = time.time() + 120
            while len(got) < 3 + frames and time.time() < deadline:
                time.sleep(0.002)
            dt = time.perf_counter() - t0
            assert len(got) == 3 + frames, f"{lane}: lost frames"
            return dt / frames
        finally:
            for b in (rx, tx):
                if b is not None:
                    b.stop()
            hub.stop()

    # ABAB interleave, best-of to shed scheduler noise
    per_frame = {"tcp": [], "shm": []}
    for _ in range(3):
        for k in ("tcp", "shm"):
            per_frame[k].append(arm(k))
    best = {k: min(v) for k, v in per_frame.items()}
    return {
        "frame_bytes": 262146 * 4,
        "per_frame_s": per_frame,
        "best_per_frame_s": best,
        "shm_speedup_mechanism": (round(best["tcp"] / best["shm"], 3)
                                  if best["shm"] else None),
        "note": "sender->hub->receiver, 2 hops; best-of-3 per arm "
                "(min sheds 1-core scheduler noise)",
    }


def run_digests(args) -> dict:
    """Delta-vs-full byte identity at the matched chain codec — the
    'delta is a pure wire change' proof at federation scale (the
    tier-1 pins cover it at 2 clients; this is the 8-client re-run
    recorded in the artifact)."""
    delta = _one("pin_delta", clients=8, rounds=3, seed=args.seed,
                 input_dim=4096, train_samples=30, lane="shm",
                 bcast="delta")
    full = _one("pin_full_chain", clients=8, rounds=3, seed=args.seed,
                input_dim=4096, train_samples=30, lane="tcp",
                bcast="full", bcast_codec="qsgd8")
    same = (delta["digests"] == full["digests"]
            and bool(delta["digests"]))
    return {
        "delta_arm": {k: delta[k] for k in ("tag", "rounds", "nan_free")},
        "full_chain_arm": {k: full[k] for k in ("tag", "rounds",
                                                "nan_free")},
        "clients": 8,
        "digests_identical": same,
        "ok": bool(same),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mode",
                   choices=["ab32", "big256", "digests", "micro", "all"],
                   default="all")
    p.add_argument("--out", default="FEDXPORT_r13.json")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--ab-clients", type=int, default=32)
    p.add_argument("--ab-rounds", type=int, default=7)
    p.add_argument("--input-dim", type=int, default=131072)
    p.add_argument("--train-samples", type=int, default=16)
    p.add_argument("--big-clients", type=int, default=256)
    p.add_argument("--big-rounds", type=int, default=6)
    p.add_argument("--big-reps", type=int, default=3)
    args = p.parse_args(argv)

    artifact = {}
    if os.path.exists(args.out):
        # partial re-runs (--mode big256 after an earlier --mode ab32)
        # MERGE into the existing artifact instead of erasing sections
        try:
            with open(args.out) as fh:
                artifact = json.load(fh)
        except (OSError, json.JSONDecodeError):
            artifact = {}
    artifact["experiment"] = (
        "raw-speed transport rework: shared-memory ring lanes for "
        "co-located peers (payloads through slab rings, headers + "
        "fallback on TCP) and int8 delta broadcast against "
        "last-acked rounds (quantized chain + downlink EF)"
    )
    artifact["generated_unix"] = round(time.time(), 1)
    ok = True
    if args.mode in ("digests", "all"):
        artifact["digest_pins"] = run_digests(args)
        ok = ok and artifact["digest_pins"]["ok"]
    if args.mode in ("ab32", "all"):
        artifact["ab32"] = run_ab32(args)
        ok = ok and artifact["ab32"]["ok"]
    if args.mode in ("micro", "all"):
        artifact["micro"] = run_micro(args)
    if args.mode in ("big256", "all"):
        artifact["big256"] = run_big256(args)
        ok = ok and artifact["big256"]["ok"]
    with open(args.out, "w") as fh:
        json.dump(artifact, fh, indent=1, default=float)
    print(json.dumps({"out": args.out, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
