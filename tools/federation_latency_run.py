#!/usr/bin/env python
"""Round-latency + broadcast-bytes evidence for the wire hot-path
overhaul (hub multicast, encode-once broadcast, streaming aggregation).

Both arms run THIS commit — the legacy arm flips the server's
``--hotpath legacy`` knob, which restores the pre-overhaul behavior
exactly (per-node unicast re-encoded sync frames through the hub's
serial forward, buffered close-time aggregation), so before/after is a
same-commit controlled comparison:

1. ``legacy`` — per-node unicast broadcast + buffered aggregation;
2. ``fast``   — ``__hub__: mcast`` fan-out (one payload + receiver
   list, per-connection send queues drained by the hub's sender pool),
   encode-once zero-copy sync frames, streaming (sum n·model, sum n)
   aggregation folded on arrival.

Each federation is hub + server + N client OS processes over real TCP
(``experiments/distributed_fedavg.py``) with a ≥1 MB model
(``logistic_regression(--input-dim, 2)``; 131072 → 1.05 MB fp32) in a
comm-dominant regime (``--train-samples 16`` = one local batch), at 16
and 32 clients, codec off and on (qsgd int8 deltas).

Measurements (per arm):

- per-round wall-clock p50/p95/max from the server ``round_log`` close
  stamps (t-deltas — the same series ``tools/trace_summary.py`` reports);
- server→hub broadcast bytes per round: the server process's exact
  ``comm.sent_bytes{msg_type=S2C_INIT_CONFIG|S2C_SYNC_MODEL}`` counters;
- upload bytes (unchanged by this PR — a control);
- client upload digests across a same-seed re-run (int8 arm):
  determinism must be byte-identical.

Pre-declared thresholds (16 clients, codec off):

- broadcast bytes/round reduced >= 5x  (multicast vs per-node unicast);
- p50 per-round wall-clock reduced >= 20% (fast <= 0.8x legacy);
- int8 re-run digests byte-identical.

Each arm's round_log is also dumped to ``tools/logs/fedlat_<arm>.jsonl``
so ``python tools/trace_summary.py`` renders the same round-latency
section from the raw records.

Usage: python tools/federation_latency_run.py
       [--clients 16] [--rounds 7] [--input-dim 131072]
       [--skip-32] [--out FEDLAT_r07.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BCAST_KEYS = ("comm.sent_bytes{msg_type=S2C_INIT_CONFIG}",
              "comm.sent_bytes{msg_type=S2C_SYNC_MODEL}")

# the same nearest-rank estimator trace_summary reports — ONE
# definition, so the artifact and the report can't disagree on a delta
from tools.trace_summary import percentile as _percentile  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--rounds", type=int, default=7)
    p.add_argument("--input-dim", type=int, default=131072)
    p.add_argument("--train-samples", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--round-timeout", type=float, default=180.0)
    p.add_argument("--skip-32", action="store_true",
                   help="skip the 32-client arms (slow-box escape hatch)")
    p.add_argument("--out", default="FEDLAT_r07.json")
    args = p.parse_args()

    import numpy as np

    from fedml_tpu.experiments.distributed_fedavg import launch

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    log_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "logs")
    os.makedirs(log_dir, exist_ok=True)

    def run_one(tag, clients, hotpath, codec):
        info = {}
        out_npz = f"/tmp/fedlat_{tag}.npz"
        t0 = time.time()
        rc = launch(
            num_clients=clients, rounds=args.rounds, seed=args.seed,
            batch_size=args.batch_size, out_path=out_npz,
            round_timeout=args.round_timeout,
            codec=codec, wire=2, input_dim=args.input_dim,
            hotpath=hotpath, train_samples=args.train_samples,
            info=info, env=env,
            timeout=600.0 + args.rounds * args.round_timeout,
        )
        if rc != 0:
            raise SystemExit(f"{tag}: server subprocess failed rc={rc}")
        wall = round(time.time() - t0, 1)
        z = np.load(out_npz)
        round_log = json.loads(str(z["round_log"]))
        with open(os.path.join(log_dir, f"fedlat_{tag}.jsonl"), "w") as fh:
            for rec in round_log:
                fh.write(json.dumps(rec) + "\n")
        stamps = [r["t"] for r in round_log
                  if isinstance(r.get("t"), (int, float))]
        deltas = [round(b - a, 4) for a, b in zip(stamps, stamps[1:])]
        aggs = [r["time_agg"] for r in round_log
                if isinstance(r.get("time_agg"), (int, float))]
        comm = info.get("comm_bytes", {})
        bcast = sum(comm.get(k, 0) for k in BCAST_KEYS)
        c2s = comm.get("comm.recv_bytes{msg_type=C2S_SEND_MODEL}", 0)
        digests = {k: v for k, v in info.items()
                   if k.endswith("_upload_digest")}
        return {
            "clients": clients,
            "hotpath": hotpath,
            "codec": codec,
            "rounds": info.get("rounds"),
            "wall_s": wall,
            "round_wall_s": {
                "samples": deltas,
                "p50": _percentile(deltas, 0.50),
                "p95": _percentile(deltas, 0.95),
                "max": max(deltas) if deltas else None,
            },
            "close_agg_s": {
                "mean": round(sum(aggs) / len(aggs), 6) if aggs else None,
                "max": round(max(aggs), 6) if aggs else None,
            },
            "broadcast_bytes_total": bcast,
            "broadcast_bytes_per_round": round(bcast / args.rounds, 1),
            "c2s_upload_bytes_total": c2s,
            "client_upload_digests": digests,
        }

    arms = {}
    arms["legacy_16"] = run_one("legacy_16", args.clients, "legacy", "none")
    arms["fast_16"] = run_one("fast_16", args.clients, "fast", "none")
    arms["legacy_16_int8"] = run_one("legacy_16_int8", args.clients,
                                     "legacy", "int8")
    arms["fast_16_int8"] = run_one("fast_16_int8", args.clients,
                                   "fast", "int8")
    arms["fast_16_int8_rerun"] = run_one("fast_16_int8_rerun", args.clients,
                                         "fast", "int8")
    if not args.skip_32:
        arms["legacy_32"] = run_one("legacy_32", 32, "legacy", "none")
        arms["fast_32"] = run_one("fast_32", 32, "fast", "none")

    base, fast = arms["legacy_16"], arms["fast_16"]
    bytes_ratio = (base["broadcast_bytes_per_round"]
                   / fast["broadcast_bytes_per_round"]
                   if fast["broadcast_bytes_per_round"] else None)
    p50_base = base["round_wall_s"]["p50"]
    p50_fast = fast["round_wall_s"]["p50"]
    p50_speedup = (p50_base / p50_fast if p50_fast else None)
    digests_match = (
        bool(arms["fast_16_int8"]["client_upload_digests"])
        and arms["fast_16_int8"]["client_upload_digests"]
        == arms["fast_16_int8_rerun"]["client_upload_digests"]
    )
    params = args.input_dim * 2 + 2
    artifact = {
        "experiment": (
            f"wire hot-path latency on the real TCP hub: hub + server + "
            f"N client OS processes, logistic_regression({args.input_dim},"
            f" 2) ({params} params, {params * 4 / 1e6:.2f} MB fp32), "
            f"{args.rounds} rounds, --train-samples "
            f"{args.train_samples} (comm-dominant regime); legacy arm = "
            f"--hotpath legacy on the SAME commit (per-node unicast + "
            f"buffered aggregation, the pre-overhaul wire path)"
        ),
        "thresholds_pre_declared": {
            "broadcast_bytes_ratio_min": 5.0,
            "p50_round_wall_reduction_min": 0.20,
            "upload_digests_bit_identical": True,
        },
        "arms": arms,
        "verdict": {
            "broadcast_bytes_per_round": {
                "legacy": base["broadcast_bytes_per_round"],
                "fast": fast["broadcast_bytes_per_round"],
                "ratio": round(bytes_ratio, 2) if bytes_ratio else None,
                "ok": bool(bytes_ratio and bytes_ratio >= 5.0),
            },
            "p50_round_wall_s": {
                "legacy": p50_base,
                "fast": p50_fast,
                "speedup": round(p50_speedup, 3) if p50_speedup else None,
                "reduction": (round(1 - p50_fast / p50_base, 3)
                              if p50_base and p50_fast else None),
                "ok": bool(p50_speedup and p50_speedup >= 1.25),
            },
            "encoded_uploads_bit_identical_across_reruns": digests_match,
        },
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    v = artifact["verdict"]
    print(json.dumps({"out": args.out,
                      "bytes_ratio": v["broadcast_bytes_per_round"]["ratio"],
                      "p50_legacy": p50_base, "p50_fast": p50_fast,
                      "p50_speedup": v["p50_round_wall_s"]["speedup"],
                      "digests_match": digests_match}))
    if not (v["broadcast_bytes_per_round"]["ok"]
            and v["p50_round_wall_s"]["ok"] and digests_match):
        raise SystemExit("federation latency verdict FAILED")


if __name__ == "__main__":
    main()
