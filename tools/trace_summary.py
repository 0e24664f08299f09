#!/usr/bin/env python
"""Trace-analysis CLI for the observability layer (fedml_tpu/obs).

Reads one or more ``metrics.jsonl`` streams (pass a run dir or the file
itself) and prints, per input:

- the per-round span breakdown (``time_sample/pack/round/eval/agg`` —
  the reference's scattered manual timers, centralized);
- comm byte / message / latency tables per message type
  (``comm.sent_bytes{msg_type=...}`` naming convention);
- the compile-event timeline (``kind=compile`` records +
  ``jax.compiles{fn=...}`` counters — a recompile storm shows up as a
  count climbing with rounds);
- gauges (device-memory high-water etc.).

``--json`` emits one machine-parseable JSON object so BENCH_* rounds
can consume the same numbers the human table shows.  Deliberately
stdlib-only: usable on any checkout with a bare python, no jax import.

Usage:
    python tools/trace_summary.py runs/fedavg-synthetic-20260803-120000
    python tools/trace_summary.py --json run_a run_b
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, List, Optional, Tuple


def parse_metric_key(key: str) -> Tuple[str, Dict[str, str]]:
    """``name{k=v,...}`` → (name, labels) — mirror of obs.telemetry
    (duplicated so this CLI never needs the package importable)."""
    if not key.endswith("}") or "{" not in key:
        return key, {}
    name, _, inner = key.partition("{")
    labels = {}
    for part in inner[:-1].split(","):
        if "=" in part:
            k, _, v = part.partition("=")
            labels[k] = v
    return name, labels


def load_records(path: str) -> List[dict]:
    if os.path.isdir(path):
        path = os.path.join(path, "metrics.jsonl")
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # partial last line of a crashed run: skip, keep rest
    return records


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of raw samples (round wall times are a
    handful of exact numbers, not histogram buckets)."""
    if not values:
        return None
    vals = sorted(values)
    idx = min(len(vals) - 1, max(0, int(math.ceil(q * len(vals))) - 1))
    return vals[idx]


def hist_quantile(hist: dict, q: float) -> Optional[float]:
    """Upper-bound estimate of a quantile from the log2 bucket counts."""
    count = hist.get("count", 0)
    if not count:
        return None
    buckets = sorted(
        (float(le), n) for le, n in (hist.get("buckets") or {}).items()
    )
    target = q * count
    seen = 0
    for le, n in buckets:
        seen += n
        if seen >= target:
            return le
    return buckets[-1][0] if buckets else None


def summarize(records: List[dict]) -> dict:
    rounds = [r for r in records if "round" in r and "kind" not in r]
    compiles = [r for r in records if r.get("kind") == "compile"]
    traces = [r for r in records if r.get("kind") == "trace"]
    config = next((r for r in records if r.get("kind") == "config"), None)
    telemetry = None
    for r in records:
        if r.get("kind") == "telemetry":
            telemetry = r  # last snapshot wins (counters are cumulative)

    span_keys = sorted({k for r in rounds for k in r if k.startswith("time_")})
    spans = {}
    for k in span_keys:
        vals = [r[k] for r in rounds if isinstance(r.get(k), (int, float))]
        if vals:
            spans[k] = {
                "count": len(vals),
                "total_s": sum(vals),
                "mean_s": sum(vals) / len(vals),
                "max_s": max(vals),
            }

    comm: Dict[str, dict] = {}
    gauges: Dict[str, float] = {}
    compile_counters: Dict[str, float] = {}
    faults: Dict[str, float] = {}
    # fault/degradation series (the chaos layer's accounting): injected
    # faults, what the tolerance layer observed, degraded rounds, and
    # the comm-resilience counters (retries/reconnects/hub drops)
    _FAULT_PREFIXES = ("faults.", "hub.", "rounds.", "robust.")
    _FAULT_COMM = ("comm.unhandled_msgs", "comm.send_retries",
                   "comm.send_failed", "comm.reconnects")
    if telemetry:
        for key, value in (telemetry.get("counters") or {}).items():
            name, labels = parse_metric_key(key)
            if name.startswith(_FAULT_PREFIXES) or name in _FAULT_COMM:
                faults[key] = value
            if name.startswith("comm."):
                row = comm.setdefault(labels.get("msg_type", "?"), {})
                row[name.split(".", 1)[1]] = value
            elif name.startswith("jax."):
                compile_counters[key] = value
        for key, value in (telemetry.get("gauges") or {}).items():
            gauges[key] = value
        for key, hist in (telemetry.get("hists") or {}).items():
            name, labels = parse_metric_key(key)
            if name == "comm.send_latency_s":
                row = comm.setdefault(labels.get("msg_type", "?"), {})
                row["send_latency"] = {
                    "count": hist.get("count"),
                    "mean_s": hist.get("mean"),
                    "p50_le_s": hist_quantile(hist, 0.5),
                    "p99_le_s": hist_quantile(hist, 0.99),
                    "max_s": hist.get("max"),
                }
            elif name == "comm.handle_latency_s":
                row = comm.setdefault(labels.get("msg_type", "?"), {})
                row["handle_latency"] = {
                    "count": hist.get("count"),
                    "mean_s": hist.get("mean"),
                    "max_s": hist.get("max"),
                }
            elif name in ("span.reconnect_s", "span.server_round_s",
                          "robust.upload_norm"):
                # recovery spans: how long nodes were off the hub / how
                # long the server's rounds ran open (deadline closes
                # show up as max ~= round_timeout); robust.upload_norm
                # is the defense layer's delta-norm distribution (an
                # attack shows up as max >> mean)
                faults[key] = {
                    "count": hist.get("count"),
                    "mean_s": hist.get("mean"),
                    "max_s": hist.get("max"),
                }

    # degraded/resume events ride the record stream (kind-tagged)
    fault_events = [r for r in records
                    if r.get("kind") in ("degraded_round", "resume")]

    # per-round defense activity (robust aggregation): round_close
    # events carry a ``defense`` dict when a defense is configured —
    # clipped / outlier-rejected / DP-noised uploads and capped
    # connections, per round, next to the cumulative robust.* counters
    defense_rounds = [
        {"round": r.get("round"), **r["defense"]}
        for r in records
        if r.get("kind") == "round_close" and isinstance(
            r.get("defense"), dict)
    ]

    # round latency from the server round_log close stamps ("t"): the
    # delta between consecutive closes is one round's wall time — the
    # same numbers FEDLAT artifacts and chaos soaks report, so both
    # read this one section
    stamps = [r["t"] for r in rounds
              if isinstance(r.get("t"), (int, float))]
    deltas = [b - a for a, b in zip(stamps, stamps[1:])]
    round_latency = None
    if deltas:
        round_latency = {
            "rounds_timed": len(deltas),
            "p50_s": percentile(deltas, 0.50),
            "p95_s": percentile(deltas, 0.95),
            "max_s": max(deltas),
            "mean_s": sum(deltas) / len(deltas),
        }
    # span.agg_s trend vs realized cohort size: close-time aggregation
    # cost per participant count (the buffered-vs-streaming stall shows
    # up here as mean_agg_s growing with K)
    agg_by_cohort: Dict[int, dict] = {}
    for r in rounds:
        if (isinstance(r.get("time_agg"), (int, float))
                and isinstance(r.get("participants"), list)):
            row = agg_by_cohort.setdefault(
                len(r["participants"]),
                {"count": 0, "total_agg_s": 0.0, "max_agg_s": 0.0})
            row["count"] += 1
            row["total_agg_s"] += r["time_agg"]
            row["max_agg_s"] = max(row["max_agg_s"], r["time_agg"])
    for row in agg_by_cohort.values():
        row["mean_agg_s"] = row["total_agg_s"] / row["count"]

    # transport split (shm lane + delta broadcast, PR 13): how many of
    # the wire bytes rode shared-memory rings vs inline TCP, what the
    # delta broadcast shipped vs fell back on, and the lane's fallback
    # reasons — the raw-speed levers' accounting in one place
    transport = {}
    if telemetry:
        ctr = telemetry.get("counters") or {}
        sent = recv = shm = 0.0
        shm_fallbacks = {}
        delta_fallbacks = {}
        for key, value in ctr.items():
            name, labels = parse_metric_key(key)
            if name == "comm.sent_bytes":
                sent += value
            elif name == "comm.recv_bytes":
                recv += value
            elif name == "comm.shm_bytes":
                shm += value
            elif name == "comm.shm_fallbacks":
                shm_fallbacks[labels.get("reason", "?")] = value
            elif name == "comm.delta_full_fallbacks":
                delta_fallbacks[labels.get("reason", "?")] = value
        total = sent + recv
        if shm or shm_fallbacks or any(
            parse_metric_key(k)[0].startswith("comm.delta_")
            for k in ctr
        ):
            transport = {
                "wire_bytes_total": total,
                "shm_payload_bytes": shm,
                "shm_share": (shm / total) if total else None,
                "tcp_inline_bytes": max(0.0, total - shm),
                "shm_frames": sum(
                    v for k, v in ctr.items()
                    if parse_metric_key(k)[0] == "comm.shm_frames"),
                "shm_fallbacks": shm_fallbacks,
                "delta_bcast_bytes": ctr.get("comm.delta_bcast_bytes", 0),
                "delta_full_fallbacks": delta_fallbacks,
                "delta_resyncs": ctr.get("comm.delta_resyncs", 0),
            }

    # compression ratios: the comm.raw_bytes / comm.compressed_bytes
    # counter pair the compress subsystem records per message type
    compression = {}
    for mt, row in comm.items():
        raw, comp = row.get("raw_bytes"), row.get("compressed_bytes")
        if raw and comp:
            compression[mt] = {
                "raw_bytes": raw,
                "compressed_bytes": comp,
                "ratio": raw / comp,
            }

    return {
        "num_records": len(records),
        "num_rounds": len(rounds),
        "round_latency": round_latency,
        "agg_by_cohort": agg_by_cohort,
        "config": {k: config[k] for k in ("algorithm", "dataset", "model")
                   if config and k in config} if config else {},
        "rounds": rounds,
        "spans": spans,
        "comm": comm,
        "transport": transport,
        "compression": compression,
        "faults": faults,
        "fault_events": fault_events,
        "defense_rounds": defense_rounds,
        "compiles": [
            {k: c.get(k) for k in ("ts", "fn", "signature", "seconds")}
            for c in compiles
        ],
        "compile_counters": compile_counters,
        "gauges": gauges,
        "traces": traces,
    }


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n):,} B"
        n /= 1024.0
    return f"{n:,.1f} GiB"


def _fmt_s(v) -> str:
    if v is None:
        return "-"
    if v >= 1.0:
        return f"{v:,.2f}s"
    if v >= 1e-3:
        return f"{v * 1e3:,.2f}ms"
    return f"{v * 1e6:,.0f}µs"


def render_text(path: str, s: dict, max_round_rows: int = 30) -> None:
    print(f"== {path} ==")
    if s["config"]:
        print("  config: " + ", ".join(f"{k}={v}" for k, v in s["config"].items()))
    print(f"  records: {s['num_records']}  rounds: {s['num_rounds']}")

    rounds = s["rounds"]
    span_keys = sorted(s["spans"])
    if rounds and span_keys:
        print("\n  per-round spans:")
        header = "    round  " + "".join(f"{k[5:]:>12}" for k in span_keys)
        print(header)
        shown = rounds if len(rounds) <= max_round_rows else (
            rounds[: max_round_rows // 2] + rounds[-max_round_rows // 2:]
        )
        prev_r = None
        for r in shown:
            if prev_r is not None and r.get("round", 0) > prev_r + 1:
                print("    ...")
            prev_r = r.get("round", 0)
            cells = "".join(
                f"{_fmt_s(r.get(k)) if isinstance(r.get(k), (int, float)) else '-':>12}"
                for k in span_keys
            )
            print(f"    {r.get('round', '?'):>5}  {cells}")
        total = "".join(
            f"{_fmt_s(s['spans'][k]['total_s']):>12}" for k in span_keys
        )
        mean = "".join(
            f"{_fmt_s(s['spans'][k]['mean_s']):>12}" for k in span_keys
        )
        print(f"    total  {total}")
        print(f"    mean   {mean}")

    if s.get("round_latency"):
        rl = s["round_latency"]
        print("\n  round latency (close-to-close wall time, "
              f"{rl['rounds_timed']} rounds):")
        print(f"    p50 {_fmt_s(rl['p50_s'])}  p95 {_fmt_s(rl['p95_s'])}  "
              f"max {_fmt_s(rl['max_s'])}  mean {_fmt_s(rl['mean_s'])}")
    if s.get("agg_by_cohort"):
        print("\n  close-time aggregation vs cohort size:")
        for k in sorted(s["agg_by_cohort"]):
            row = s["agg_by_cohort"][k]
            print(f"    K={k:<4} rounds={row['count']:<4}"
                  f"mean {_fmt_s(row['mean_agg_s'])}  "
                  f"max {_fmt_s(row['max_agg_s'])}")

    if s["comm"]:
        print("\n  comm (per message type):")
        print(f"    {'msg_type':<20}{'sent':>8}{'sent_bytes':>14}"
              f"{'recv':>8}{'recv_bytes':>14}{'send p50':>10}{'send p99':>10}")
        for mt in sorted(s["comm"]):
            row = s["comm"][mt]
            lat = row.get("send_latency") or {}
            print(
                f"    {mt:<20}"
                f"{int(row.get('sent_msgs', 0)):>8}"
                f"{_fmt_bytes(row.get('sent_bytes', 0)):>14}"
                f"{int(row.get('recv_msgs', 0)):>8}"
                f"{_fmt_bytes(row.get('recv_bytes', 0)):>14}"
                f"{_fmt_s(lat.get('p50_le_s')):>10}"
                f"{_fmt_s(lat.get('p99_le_s')):>10}"
            )

    if s.get("transport"):
        t = s["transport"]
        print("\n  transport (shm lane / delta broadcast):")
        share = t.get("shm_share")
        print(f"    wire bytes {_fmt_bytes(t['wire_bytes_total']):>14}  "
              f"shm {_fmt_bytes(t['shm_payload_bytes']):>14}"
              + (f" ({share * 100:.1f}%)" if share is not None else "")
              + f"  inline tcp {_fmt_bytes(t['tcp_inline_bytes']):>14}")
        print(f"    shm frames {int(t.get('shm_frames', 0))}"
              + (f"  fallbacks {t['shm_fallbacks']}"
                 if t.get("shm_fallbacks") else ""))
        if t.get("delta_bcast_bytes") or t.get("delta_full_fallbacks") \
                or t.get("delta_resyncs"):
            print(f"    delta bcast {_fmt_bytes(t['delta_bcast_bytes'])}"
                  f"  full fallbacks {t.get('delta_full_fallbacks') or {}}"
                  f"  resyncs {int(t.get('delta_resyncs', 0))}")

    if s.get("compression"):
        print("\n  compression (per message type):")
        for mt in sorted(s["compression"]):
            row = s["compression"][mt]
            print(f"    {mt:<20}raw {_fmt_bytes(row['raw_bytes']):>14}"
                  f"  wire {_fmt_bytes(row['compressed_bytes']):>14}"
                  f"  ratio {row['ratio']:>6.2f}x")

    if s["compiles"] or s["compile_counters"]:
        print("\n  compile events:")
        for c in s["compiles"]:
            print(f"    ts={c.get('ts', 0):.3f}  fn={c.get('fn')}  "
                  f"signature#{c.get('signature')}  {_fmt_s(c.get('seconds'))}")
        for key in sorted(s["compile_counters"]):
            print(f"    {key} = {s['compile_counters'][key]:g}")

    if s.get("faults") or s.get("fault_events"):
        print("\n  faults / degradation:")
        for key in sorted(s.get("faults") or {}):
            v = s["faults"][key]
            if isinstance(v, dict):
                # robust.upload_norm is a unitless L2 norm, not seconds
                fmt = ((lambda x: "-" if x is None else f"{x:g}")
                       if "upload_norm" in key else _fmt_s)
                print(f"    {key}: count={v.get('count')} "
                      f"mean={fmt(v.get('mean_s'))} "
                      f"max={fmt(v.get('max_s'))}")
            else:
                print(f"    {key} = {v:g}")
        for ev in s.get("fault_events") or []:
            extra = {k: v for k, v in ev.items() if k not in ("kind", "ts")}
            print(f"    event {ev.get('kind')}: {extra}")

    if s.get("defense_rounds"):
        print("\n  robust aggregation (per round):")
        print("    round  clipped  outliers  dp_noised  capped_conns")
        for d in s["defense_rounds"]:
            print(f"    {str(d.get('round')):<6} {d.get('clipped', 0):<8} "
                  f"{d.get('outliers', 0):<9} {d.get('dp_noised', 0):<10} "
                  f"{d.get('capped_conns', 0)}"
                  + ("  CAP-INFEASIBLE" if d.get("cap_infeasible") else ""))

    if s["gauges"]:
        print("\n  gauges:")
        for key in sorted(s["gauges"]):
            v = s["gauges"][key]
            shown = _fmt_bytes(v) if "bytes" in key else f"{v:g}"
            print(f"    {key} = {shown}")

    if s["traces"]:
        print("\n  profiler traces:")
        for t in s["traces"]:
            extra = f"  round_s={t['round_s']}" if "round_s" in t else ""
            print(f"    {t.get('trace_dir')}{extra}")
    print()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("inputs", nargs="+",
                   help="run dir(s) containing metrics.jsonl, or file paths")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-parseable output (one object, keyed by input)")
    args = p.parse_args(argv)

    out = {}
    errors = 0
    for path in args.inputs:
        try:
            records = load_records(path)
        except OSError as e:
            print(f"error: {path}: {e}", file=sys.stderr)
            errors += 1
            continue
        out[path] = summarize(records)

    if args.as_json:
        # strict JSON for machine consumers: python's json would emit
        # bare Infinity/NaN tokens, which most parsers reject
        def _clean(v):
            if isinstance(v, dict):
                return {k: _clean(x) for k, x in v.items()}
            if isinstance(v, list):
                return [_clean(x) for x in v]
            if isinstance(v, float) and not math.isfinite(v):
                return None
            return v

        print(json.dumps(_clean(out), default=str))
    else:
        for path, s in out.items():
            render_text(path, s)
    return 2 if errors else 0  # partial failure is failure (BENCH harnesses)


if __name__ == "__main__":
    sys.exit(main())
