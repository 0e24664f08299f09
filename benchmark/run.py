"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that builds the cell from its files (``cells.py``), warms up
its one program, checks one round against the plain reference, measures for
``--seconds`` and prints one JSON line last.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces a few steady calls and
reports its per-layer metrics.  Without a TPU it exits non-zero and prints no
result.  ``--rehearsal`` runs the same code on the CPU at the toy geometry of
the workload file's ``rehearsal`` block: every line it prints carries
``platform=cpu rehearsal``, and there is no metric and no result line.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up counts from here: imports are part of it

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
REHEARSAL_TAG = "platform=cpu rehearsal"
WARMUP_CALLS = 2  # the first compiles or loads; the second runs steady
TRACED_CALLS = 3  # "a few calls": a whole window's trace would not come back


class CompileWatch:
    """Backend compilations and persistent-cache hits of this process
    (``jax.monitoring``; copied from ``chip_smoke.Watch``).  The event
    fires once per XLA program, compiled or loaded from the cache."""

    def __init__(self):
        from jax import monitoring

        self.compiles = []  # (wall time, seconds)
        self.cache = {"hits": 0, "misses": 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.compiles.append((time.time(), float(duration)))

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache["misses"] += 1


def check_reference(cell, session, seed: int) -> dict:
    """One round of the system on the reduced cohort against the plain
    reference (``reference.py``), from the session's current state, which
    it leaves whole.  The system's new variables wait on the host while the
    reference runs: it needs the room (12-16 bytes a parameter beside the
    state), and ``compare`` takes them back when its trees are gone."""
    import jax
    import numpy as np

    from benchmark import reference

    block = reference.reference_block(cell.config, cell.reference, seed)
    old = session.state
    new_vars, metrics = session.reference_round(block)
    new_vars = jax.device_get(new_vars)  # and the device's copy is dropped
    sys_loss = float(np.sum(metrics["loss_sum"]) / np.sum(metrics["count"]))
    ref_delta, ref_loss = reference.reference_round(
        session.bundle, cell.config, old.variables, old.key,
        session.round_idx(), block)
    return reference.compare(old.variables, new_vars, ref_delta, sys_loss,
                             ref_loss)


def timed_call(session):
    """One call into the program, fully synced: block on the new state and
    read every metric back.  Returns (start, end, rounds, metrics)."""
    t0 = time.perf_counter()
    rounds, metrics = session.call()
    t1 = time.perf_counter()
    return t0, t1, rounds, metrics


def call_ok(metrics: dict, cohort: int) -> bool:
    import numpy as np

    return bool(all(np.isfinite(v).all() for v in metrics.values())
                and (metrics["participants"] == cohort).all())


def measure(session, seconds: float) -> list:
    """Calls that start and end inside a window of ``seconds``: a call is
    not started when the median call so far would not finish in time."""
    calls = []
    t_end = time.perf_counter() + seconds
    while True:
        typical = statistics.median(c[1] - c[0] for c in calls) if calls else 0
        if time.perf_counter() + typical > t_end:
            return calls
        calls.append(timed_call(session))


def traced(session, trace_dir: str) -> tuple:
    """``TRACED_CALLS`` steady calls under the profiler, each inside a
    ``bench.call`` annotation on the trace's own clock."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    calls = []
    with jax.profiler.trace(trace_dir):
        for _ in range(TRACED_CALLS):
            with jax.profiler.TraceAnnotation("bench.call"):
                calls.append(timed_call(session))
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return calls, path


def memory_stats(devices) -> dict:
    """Of the fullest chip: its peak of live buffers plus its peak of scratch
    reserved for loaded programs.  The two regions are apart (after a run the
    limit less both leaves what the allocator reports free), but the two
    peaks need not fall in the same moment, so their sum is an upper bound
    of the chip's high-water.  It is the high-water itself where the live
    peak is reached inside a call of the largest program, as it is when the
    calls raise ``peak_bytes_in_use`` above what set-up left
    (``live_peak_before_calls`` in the result's ``detail``)."""
    stats = [d.memory_stats() or {} for d in devices]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0)
               + s.get("peak_bytes_reserved", 0))
    return {"peak_bytes": int(full.get("peak_bytes_in_use", 0)
                              + full.get("peak_bytes_reserved", 0)),
            **{k: int(v) for k, v in full.items()
               if isinstance(v, (int, float))}}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args()

    from benchmark import cells

    cell = cells.load_cell(args.workload, rehearsal=args.rehearsal)
    manifest = cells.manifest()
    seconds = (args.seconds if args.seconds is not None
               else manifest["run_seconds"])
    say = print
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
        say = lambda *a: print(REHEARSAL_TAG, *a, flush=True)  # noqa: E731

    import jax
    import numpy as np

    from fedml_tpu.utils.compile_cache import configure_compile_cache

    # every program is worth caching: a run after the first compiles nothing
    cache_dir = configure_compile_cache(min_compile_secs=0.0)
    watch = CompileWatch()
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearsal and platform != "tpu":
        print(f"benchmark/run.py measures a TPU and found platform="
              f"{platform!r} ({devices[0].device_kind}, {len(devices)} "
              "device(s)); no result (--rehearsal runs the toy geometry on "
              "the CPU)", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chip(s), found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]

    phases = {"start_s": time.time() - T_START}
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, args.seed, devices)
    phases["session_s"] = time.time() - T_START
    live_before = memory_stats(devices).get("peak_bytes_in_use", 0)
    for _ in range(WARMUP_CALLS):
        warm = timed_call(session)
    round_idx0 = session.round_idx()
    n_compiles = len(watch.compiles)
    setup_s = time.time() - T_START

    if args.trace:
        trace_dir = os.path.join(REPO, "runs", "benchmark_trace", cell.name)
        calls, trace_path = traced(session, trace_dir)
    else:
        calls = measure(session, seconds)
    phases["window_end_s"] = time.time() - T_START
    compiles_in_window = len(watch.compiles) - n_compiles
    rounds_run = sum(c[2] for c in calls)
    failed = sum(not call_ok(c[3], session.cohort) for c in calls)
    rounds_short = round_idx0 + rounds_run - session.round_idx()
    # memory is read before the reference runs: the peak is the system's own
    memory = {**memory_stats(devices), "live_peak_before_calls": live_before}
    agreement = check_reference(cell, session, args.seed)
    phases["reference_end_s"] = time.time() - T_START
    # the process's peaks again: what the check itself took, where it took
    # more than the calls (a peak never falls)
    after = memory_stats(devices)
    reference_memory = {f"reference_{k}": after.get(k, 0) for k in (
        "peak_bytes_in_use", "peak_bytes_reserved")}
    correct = bool(agreement["ok"] and compiles_in_window == 0
                   and failed == 0 and calls and rounds_short == 0)

    rates = [float(np.sum(c[3]["count"])) / (c[1] - c[0]) for c in calls]
    rate = statistics.median(rates) if rates else None
    peak = memory["peak_bytes"]
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        from benchmark import trace_reduce

        try:
            summary = trace_reduce.reduce_trace(trace_path)
        except ValueError as e:
            if not args.rehearsal:
                raise
            say("trace not reduced:", e)  # the CPU has no device plane
            return 0
        ctx = trace_reduce.Context(
            summary=summary, cell=cell, session=session, calls=calls,
            device_kind=devices[0].device_kind)
        kind, read = "per_layer", lambda name: cells.load_layer_metric(
            name).read(ctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        kind, read = "end_to_end", {
            f"{cell.config['throughput_unit']}_per_s": rate,
            "peak_hbm": peak / 2**30, "setup_s": setup_s}.get
    # the cell's metrics of this kind; one that finds nothing is left out
    every_cell = [w["name"] for w in manifest["workloads"]]
    metrics = {m["name"]: {"value": read(m["name"]), "unit": m["unit"]}
               for m in manifest[kind]
               if cell.name in m.get("workloads", every_cell)}
    metrics = {k: v for k, v in metrics.items() if v["value"] is not None}

    detail = {
        "cell": cell.name, "seed": args.seed, "calls": len(calls),
        "rounds": rounds_run, "call_s": [round(c[1] - c[0], 4) for c in calls][:32],
        "warmup_call_s": round(warm[1] - warm[0], 4),
        "setup_s": round(setup_s, 2),
        "reference": agreement, "reference_memory": reference_memory,
        "compiles_in_window": compiles_in_window,
        "compile_s": round(sum(s for _, s in watch.compiles), 2),
        "cache": watch.cache, "cache_dir": cache_dir,
        "memory_stats": memory, "phases_s": {k: round(v, 2)
                                             for k, v in phases.items()},
        "units_per_s": rate,
    }
    if args.rehearsal:
        say("cell", cell.name, "correct", correct, json.dumps(detail))
        say("metrics that a chip run would report:", sorted(metrics))
        return 0 if correct else 1
    out = {"correct": correct, "attempted": len(calls), "failed": failed,
           "metrics": metrics, "device": device, "detail": detail}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # every number ``correct`` compared, beside its limit: the last key of
    # the line, and the last lines on standard error
    compared = (
        ("delta_rel_l2", agreement["delta_rel_l2"], agreement["delta_limit"]),
        ("loss_rel", agreement["loss_rel"], agreement["loss_limit"]),
        ("state_finite", int(agreement["finite"]), 1),
        ("compiles_in_window", compiles_in_window, 0),
        ("failed_calls", failed, 0),
        ("rounds_not_counted", rounds_short, 0))
    out["checks"] = {n: {"value": v, "limit": l} for n, v, l in compared}
    print(json.dumps(out), flush=True)
    for n, v, l in compared:
        print(f"check {n} {v} limit {l}", file=sys.stderr, flush=True)
    return 0  # a result was printed; ``correct`` says whether it counts


if __name__ == "__main__":
    sys.exit(main())
