#!/usr/bin/env python
"""Benchmark trajectory: one trend table over every checked-in artifact.

The repo accumulates measurement artifacts PR after PR (BENCH_*,
FEDLAT_*, FEDSCALE_*, FEDTRACE_*, FAULTS_*, CONVERGENCE_*, COMPRESS_*,
MULTICHIP_*, SCALING_*, FEDERATION_*, FEDHEALTH_*) but until this tool
had zero trajectory tooling — answering "did round-wall p50 regress
since r07?" meant opening five JSON files by hand.  This parses them
all into one table keyed by (round, artifact) with each artifact's
headline numbers, so the trend is a single read — and CI uploads the
JSON form on every run as a downloadable trajectory artifact.

    python tools/bench_trend.py                  # table over the repo root
    python tools/bench_trend.py --json           # machine-readable records
    python tools/bench_trend.py --metric p50     # filter headline keys
    python tools/bench_trend.py --gate           # regression gate (exit 1)

``--gate`` compares each family's NEWEST artifact against the same
family's artifact from the prior round, within a per-family tolerance
(``GATE_RULES``): latency/overhead metrics must not grow past it,
accuracy/survival metrics must not shrink past it, ok-booleans must
not flip false.  Exit 1 on any warn-only regression — committed
measurements from dev machines are review prompts, not build breakers
— but a flipped ok/digest-pin boolean in a correctness family
(``ENFORCED_FAMILIES``: byte-identity pins, not timings) exits 2 and
CI fails the build on it.

Stdlib-only (runs in the CI lint job's bare interpreter).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

PREFIXES = (
    "BENCH_", "FEDLAT_", "FEDSCALE_", "FEDTRACE_", "FEDHEALTH_",
    "FAULTS_", "CONVERGENCE_", "COMPRESS_", "MULTICHIP_", "SCALING_",
    "FEDERATION_", "ROBUST_", "FEDXPORT_", "FEDCHURN_", "FEDFLIGHT_",
    "FEDTREE_", "FEDBUFF_", "FEDTRAFFIC_", "FEDSHARD_", "FEDHUB_",
)

_ROUND_RE = re.compile(r"[_-]r(\d+)")


def _round_of(fname: str):
    m = _ROUND_RE.search(fname)
    return int(m.group(1)) if m else None


def _num(v):
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def _deep_get(doc, path, default=None):
    cur = doc
    for key in path.split("."):
        if not isinstance(cur, dict) or key not in cur:
            return default
        cur = cur[key]
    return cur


def _first(doc, *paths):
    for p in paths:
        v = _deep_get(doc, p)
        if v is not None:
            return v
    return None


def _convergence_metrics(doc: dict) -> dict:
    out = {}
    arms = doc.get("arms")
    if isinstance(arms, dict):
        for arm, rec in arms.items():
            if isinstance(rec, dict):
                acc = _num(rec.get("final_test_acc") or rec.get("final_acc"))
                if acc is not None:
                    out[f"acc[{arm}]"] = acc
    runs = doc.get("runs")
    if isinstance(runs, dict):
        for arm, rec in runs.items():
            if isinstance(rec, dict):
                acc = _num(rec.get("final_test_acc") or rec.get("final_acc"))
                if acc is not None:
                    out[f"acc[{arm}]"] = acc
    for key in ("final_test_acc", "final_acc"):
        v = _num(doc.get(key))
        if v is not None:
            out["acc"] = v
    rtt = _deep_get(doc, "verdict.rounds_to_target")
    if isinstance(rtt, dict):
        for arm, v in rtt.items():
            if _num(v) is not None:
                out[f"rounds_to_target[{arm}]"] = v
    return out


def _extract(doc: dict, fname: str) -> dict:
    """Headline numbers per artifact family — tolerant by design: an
    extractor that finds nothing leaves an empty metrics dict rather
    than failing the whole table (artifact shapes evolve PR to PR)."""
    out = {}
    if fname.startswith("BENCH_"):
        # three generations of bench artifact shape: headline{}, parsed{},
        # and the bare top-level {metric, value, vs_baseline} form
        for sec in (doc.get("headline"), doc.get("parsed"), doc):
            if isinstance(sec, dict) and _num(sec.get("value")) is not None:
                name = str(sec.get("metric", "value"))
                out[name] = sec["value"]
                if _num(sec.get("vs_baseline")) is not None:
                    out["vs_baseline"] = sec["vs_baseline"]
                break
    elif fname.startswith("FEDLAT_"):
        for arm in ("striped", "whole", "legacy", "fast"):
            v = _num(_first(doc, f"arms.{arm}.p50_median_of_reps",
                            f"arms.{arm}.p50_pooled"))
            if v is not None:
                out[f"p50[{arm}]"] = v
        v = _num(_deep_get(doc, "verdict.bcast_queue_p50_s.striped"))
        if v is not None:
            out["bcast_queue_p50"] = v
        p50s = _deep_get(doc, "verdict.p50_round_wall_s")
        if isinstance(p50s, dict):
            for arm, v in p50s.items():
                if _num(v) is not None and len(out) < 6:
                    out[f"p50[{arm}]"] = v
    elif fname.startswith("FEDSCALE_"):
        out["clients"] = _num(_deep_get(doc, "scale.scale_run.clients"))
        out["scale_p50"] = _num(
            _deep_get(doc, "scale.scale_run.round_wall_s.p50"))
        out["hub_rss_ratio"] = _num(_deep_get(doc, "scale.hub_rss_ratio"))
        for arm in ("mux", "proc_fast", "proc_legacy"):
            v = _num(_deep_get(doc, f"latency_ab.verdict.{arm}_p50"))
            if v is not None:
                out[f"p50[{arm}]"] = v
    elif fname.startswith("FEDHEALTH_"):
        for k in ("p50_on", "p50_off", "overhead_ratio", "streams",
                  "slo_p50", "posthoc_p50"):
            v = _num(_deep_get(doc, f"verdict.{k}"))
            if v is not None:
                out[k] = v
        ok = _deep_get(doc, "verdict.ok")
        if ok is not None:
            out["ok"] = bool(ok)
    elif fname.startswith("FEDTRACE_"):
        for arm in ("off_16", "on_16"):
            v = _num(_first(doc, f"arms.{arm}.p50_median_of_reps",
                            f"arms.{arm}.round_wall_s.p50"))
            if v is not None:
                out[f"p50[{arm}]"] = v
    elif fname.startswith("ROBUST_"):
        for k in ("honest_acc", "undefended_acc_at_30pct",
                  "defended_acc_at_30pct", "latency_ratio",
                  "muxer_defended_acc"):
            v = _num(_deep_get(doc, f"verdict.{k}"))
            if v is not None:
                out[k] = v
        ok = _deep_get(doc, "verdict.ok")
        if ok is not None:
            out["ok"] = bool(ok)
    elif fname.startswith("FEDXPORT_"):
        for arm in ("tcp_full", "shm_full", "tcp_delta", "shm_delta"):
            v = _num(_deep_get(doc, f"ab32.p50_by_arm.{arm}"))
            if v is not None:
                out[f"p50[{arm}]"] = v
        v = _num(_deep_get(doc, "ab32.bcast_bytes_per_round.ratio"))
        if v is not None:
            out["delta_bytes_ratio"] = v
        v = _num(_deep_get(doc, "big256.shm_speedup"))
        if v is not None:
            out["shm_speedup_256"] = v
        for k in ("digest_pins", "ab32", "big256"):
            ok = _deep_get(doc, f"{k}.ok")
            if ok is not None:
                out[f"ok[{k}]"] = bool(ok)
    elif fname.startswith("FEDTREE_"):
        ladder = doc.get("ladder")
        if isinstance(ladder, list) and ladder:
            # headline = the LARGEST ladder point (the scale claim)
            pt = max((p for p in ladder if isinstance(p, dict)),
                     key=lambda p: p.get("clients") or 0, default=None)
            if pt:
                out["clients"] = _num(pt.get("clients"))
                out["root_rss_ratio"] = _num(
                    pt.get("root_rss_ratio_tree_vs_flat"))
                out["p50_factor"] = _num(pt.get("p50_factor_tree_vs_flat"))
                v = _num(_deep_get(pt, "tree.round_wall_s.p50"))
                if v is not None:
                    out["tree_p50"] = v
        ok = _deep_get(doc, "digest_pin.ok")
        if ok is not None:
            out["ok[digest_pin]"] = bool(ok)
        if doc.get("ok") is not None:
            out["ok"] = bool(doc["ok"])
    elif fname.startswith("FEDCHURN_"):
        v = _num(_deep_get(doc, "churn.node_rebinds"))
        if v is not None:
            out["node_rebinds"] = v
        v = _num(_deep_get(doc, "churn.run.hub_peak_rss_mb"))
        if v is not None:
            out["hub_rss_mb"] = v
        ok = _deep_get(doc, "churn.ok")
        if ok is not None:
            out["ok"] = bool(ok)
    elif fname.startswith("FEDFLIGHT_"):
        for k in ("p50_on", "p50_off", "overhead_ratio", "attributed"):
            v = _num(_deep_get(doc, f"verdict.{k}"))
            if v is not None:
                out[k] = v
        ok = _deep_get(doc, "verdict.ok")
        if ok is not None:
            out["ok"] = bool(ok)
    elif fname.startswith("FEDBUFF_"):
        for arm in ("sync", "async"):
            v = _num(_first(doc, f"openloop.{arm}.p99_round_s",
                            f"openloop.{arm}.round_wall_s.p99"))
            if v is not None:
                out[f"p99[{arm}]"] = v
        v = _num(_deep_get(doc, "openloop.p99_factor_sync_over_async"))
        if v is not None:
            out["p99_factor"] = v
        v = _num(_deep_get(doc, "openloop.acc_margin"))
        if v is not None:
            out["acc_margin"] = v
        for k in ("digest_pin", "determinism", "openloop"):
            ok = _deep_get(doc, f"{k}.ok")
            if ok is not None:
                out[f"ok[{k}]"] = bool(ok)
        if doc.get("ok") is not None:
            out["ok"] = bool(doc["ok"])
    elif fname.startswith("FEDTRAFFIC_"):
        for k in ("offline_rounds", "delayed_uploads", "rebinds",
                  "straggler_draws"):
            v = _num(_deep_get(doc, f"traffic.{k}"))
            if v is not None:
                out[k] = v
        ok = _deep_get(doc, "traffic.replay_ok")
        if ok is None:
            ok = doc.get("ok")
        if ok is not None:
            out["ok"] = bool(ok)
    elif fname.startswith("FEDSHARD_"):
        for k in ("coverage", "digest_pins", "mux_pin", "shard_bytes"):
            ok = _deep_get(doc, f"{k}.ok")
            if ok is not None:
                out[f"ok[{k}]"] = bool(ok)
        v = _num(_deep_get(doc, "throughput_256.speedup"))
        if v is not None:
            # trend-only: the 2x bar is a chip claim, recorded honestly
            # as met:false on 1-core boxes (throughput_256.note)
            out["speedup_256"] = v
        v = _num(_deep_get(doc, "coverage.fedllm.leaves_sharded"))
        if v is not None:
            out["fedllm_sharded_leaves"] = v
        if doc.get("ok") is not None:
            out["ok"] = bool(doc["ok"])
    elif fname.startswith("FEDHUB_"):
        for k in ("pins", "threads", "churn", "round_wall", "zero_copy",
                  "chaos"):
            ok = _deep_get(doc, f"{k}.ok")
            if ok is not None:
                out[f"ok[{k}]"] = bool(ok)
        v = _num(_deep_get(doc, "threads.reactor_threads_512"))
        if v is not None:
            out["threads_512"] = v
        v = _num(_deep_get(doc, "zero_copy.zero_copy_forwards"))
        if v is not None:
            out["zero_copy_forwards"] = v
        if doc.get("ok") is not None:
            out["ok"] = bool(doc["ok"])
    elif fname.startswith("FAULTS_"):
        scenarios = doc.get("scenarios")
        if isinstance(scenarios, list):
            out["scenarios"] = len(scenarios)
            out["survived"] = sum(
                1 for s in scenarios if s.get("survived"))
        out["all_nan_free"] = bool(doc.get("all_nan_free"))
    elif fname.startswith("CONVERGENCE_"):
        out.update(_convergence_metrics(doc))
    elif fname.startswith("COMPRESS_"):
        v = _num(_deep_get(doc, "verdict.reduction_ratio"))
        if v is not None:
            out["reduction_ratio"] = v
    elif fname.startswith("MULTICHIP_"):
        out["ok"] = bool(doc.get("ok"))
        if _num(doc.get("n_devices")) is not None:
            out["n_devices"] = doc["n_devices"]
    elif fname.startswith("SCALING_"):
        v = _num(_deep_get(doc, "model.headline.comm_compute_ratio_at_256"))
        if v is not None:
            out["comm_compute_ratio_at_256"] = v
    elif fname.startswith("FEDERATION_"):
        out["wall_s"] = _num(_deep_get(doc, "clean_run.total_wall_s"))
        out["oracle_ok"] = bool(_deep_get(doc, "oracle_parity.ok"))
    return {k: v for k, v in out.items() if v is not None}


def collect(root: str):
    records = []
    for prefix in PREFIXES:
        for path in sorted(glob.glob(os.path.join(root, prefix + "*.json"))):
            fname = os.path.basename(path)
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except (OSError, json.JSONDecodeError) as e:
                records.append({"artifact": fname, "round": _round_of(fname),
                                "error": f"{type(e).__name__}: {e}",
                                "metrics": {}})
                continue
            if not isinstance(doc, dict):
                continue
            records.append({
                "artifact": fname,
                "round": _round_of(fname),
                "kind": prefix.rstrip("_").lower(),
                "metrics": _extract(doc, fname),
            })
    records.sort(key=lambda r: (r["round"] if r["round"] is not None
                                else -1, r["artifact"]))
    return records


# --gate rules: family prefix -> (metric -> direction, tolerance).
# Directions: "lower" (regression = grew past tol), "higher"
# (regression = shrank past tol), "true" (regression = flipped falsy).
# Metric names ending in "*" match by prefix (per-arm keys vary).
# Only explicitly listed metrics gate — everything else is trend-only
# (ambiguous direction must never fail a build by guesswork).
GATE_RULES = {
    "FEDLAT_": ({"p50[*": "lower"}, 0.15),
    "FEDTRACE_": ({"p50[*": "lower"}, 0.15),
    "FEDSCALE_": ({"scale_p50": "lower", "hub_rss_ratio": "lower"}, 0.15),
    "FEDHEALTH_": ({"overhead_ratio": "lower", "ok": "true"}, 0.10),
    "FEDXPORT_": ({"p50[*": "lower", "delta_bytes_ratio": "lower",
                   "ok[*": "true"}, 0.15),
    "FEDCHURN_": ({"hub_rss_mb": "lower", "ok": "true"}, 0.20),
    "FEDTREE_": ({"root_rss_ratio": "lower", "p50_factor": "lower",
                  "clients": "higher", "ok": "true",
                  "ok[*": "true"}, 0.15),
    "FAULTS_": ({"survived": "higher", "all_nan_free": "true"}, 0.0),
    "ROBUST_": ({"defended_acc_at_30pct": "higher", "ok": "true"}, 0.05),
    "CONVERGENCE_": ({"acc*": "higher"}, 0.05),
    "COMPRESS_": ({"reduction_ratio": "lower"}, 0.10),
    "FEDFLIGHT_": ({"overhead_ratio": "lower",
                    "attributed": "higher", "ok": "true"}, 0.10),
    "FEDBUFF_": ({"p99_factor": "higher", "acc_margin": "higher",
                  "ok": "true", "ok[*": "true"}, 0.15),
    "FEDTRAFFIC_": ({"ok": "true"}, 0.0),
    # speedup_256 stays trend-only: it is a chip bar, honestly missed
    # on 1-core CI boxes (FEDSHARD throughput_256.note)
    "FEDSHARD_": ({"ok": "true", "ok[*": "true",
                   "fedllm_sharded_leaves": "higher"}, 0.0),
    "FEDHUB_": ({"ok": "true", "ok[*": "true", "threads_512": "lower"},
                0.10),
}

# Correctness-ENFORCING families: a flipped ok/digest-pin boolean here
# is a broken byte-identity invariant (the pins re-measure determinism,
# not speed), so the gate exits HARD (2) on it and CI fails the build —
# while latency-family breaches keep exit 1, which CI downgrades to a
# warning (committed measurements from dev machines are review prompts,
# not build breakers).  Only "true"-direction metrics enforce; numeric
# metrics inside these families stay warn-only like everywhere else.
ENFORCED_FAMILIES = {"FEDSHARD_", "FEDBUFF_", "FEDHUB_"}


def _rule_for(metric: str, rules: dict):
    if metric in rules:
        return rules[metric]
    for pat, d in rules.items():
        if pat.endswith("*") and metric.startswith(pat[:-1]):
            return d
    return None


def gate(records):
    """Newest artifact per family vs the SAME family's prior-round
    artifact -> (failures, comparisons).  Families with fewer than two
    rounds of history, unreadable artifacts, and unlisted metrics are
    skipped, never failed."""
    by_family = {}
    for r in records:
        if "error" in r or r.get("round") is None:
            continue
        fam = r.get("kind", "").upper() + "_"
        # same round + family: the lexically last artifact wins (the
        # sort in collect() already ordered them)
        by_family.setdefault(fam, {})[r["round"]] = r
    failures, comparisons = [], []
    for fam, (rules, tol) in sorted(GATE_RULES.items()):
        rounds = sorted(by_family.get(fam, {}))
        if len(rounds) < 2:
            continue
        new = by_family[fam][rounds[-1]]
        old = by_family[fam][rounds[-2]]
        for metric, nv in sorted((new.get("metrics") or {}).items()):
            direction = _rule_for(metric, rules)
            ov = (old.get("metrics") or {}).get(metric)
            if direction is None or ov is None:
                continue
            cmp = {"family": fam.rstrip("_"), "metric": metric,
                   "old": ov, "new": nv, "tolerance": tol,
                   "enforced": (fam in ENFORCED_FAMILIES
                                and direction == "true"),
                   "old_artifact": old["artifact"],
                   "new_artifact": new["artifact"]}
            if direction == "true":
                bad = bool(ov) and not bool(nv)
            elif direction == "lower":
                bad = _num(nv) is not None and _num(ov) is not None \
                    and nv > ov * (1 + tol) + 1e-12
            else:  # "higher"
                bad = _num(nv) is not None and _num(ov) is not None \
                    and nv < ov * (1 - tol) - 1e-12
            cmp["regressed"] = bad
            comparisons.append(cmp)
            if bad:
                failures.append(cmp)
    return failures, comparisons


def _fmt_val(v):
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render(records, metric_filter: str = "") -> str:
    lines = ["round  artifact                                  headline",
             "-" * 100]
    for r in records:
        metrics = r.get("metrics") or {}
        if metric_filter:
            metrics = {k: v for k, v in metrics.items()
                       if metric_filter in k}
            if not metrics:
                continue
        headline = "  ".join(f"{k}={_fmt_val(v)}"
                             for k, v in list(metrics.items())[:6])
        if "error" in r:
            headline = f"UNREADABLE ({r['error']})"
        rnd = r["round"] if r["round"] is not None else "-"
        lines.append(f"{str(rnd):<6} {r['artifact']:<41} {headline}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="artifact directory (default: the repo root)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", default="",
                   help="also write the JSON records to this path")
    p.add_argument("--metric", default="",
                   help="filter headline keys by substring (table mode)")
    p.add_argument("--gate", action="store_true",
                   help="newest-vs-prior-round regression gate; exit 1 "
                        "on any per-family tolerance breach")
    args = p.parse_args(argv)
    records = collect(args.dir)
    if not records:
        print(f"no benchmark artifacts under {args.dir!r}", file=sys.stderr)
        return 2
    if args.gate:
        failures, comparisons = gate(records)
        hard = [f for f in failures if f.get("enforced")]
        doc = {"compared": len(comparisons), "regressions": failures,
               "enforced_regressions": hard}
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(doc, fh, indent=1)
        if args.json:
            print(json.dumps(doc, indent=1))
        else:
            for c in comparisons:
                mark = "ok"
                if c["regressed"]:
                    mark = "ENFORCED" if c.get("enforced") else "REGRESSED"
                print(f"{mark:>9}  {c['family']:<12} {c['metric']:<28} "
                      f"{_fmt_val(c['old'])} -> {_fmt_val(c['new'])} "
                      f"(tol {c['tolerance']:.0%}, "
                      f"{c['old_artifact']} -> {c['new_artifact']})")
            print(f"{len(comparisons)} comparisons, "
                  f"{len(failures)} regression(s), "
                  f"{len(hard)} enforced")
        # exit 2 = enforced correctness breach (CI fails the build),
        # exit 1 = warn-only latency breach (CI logs a warning)
        return 2 if hard else (1 if failures else 0)
    doc = {"artifacts": len(records), "records": records}
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    if args.json:
        print(json.dumps(doc, indent=1))
    else:
        print(render(records, args.metric))
    return 0


if __name__ == "__main__":
    sys.exit(main())
