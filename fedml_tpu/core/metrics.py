"""Structured metrics, named timing spans, and profiler hooks.

The reference logs manual wall-clock spans to wandb/python-logging
scattered through the code (SURVEY.md §5.1/§5.5: aggregate time
``FedAVGAggregator.py:59,85-86``, message send span
``FedAvgServerManager.py:93-102``, client compute time
``MyModelTrainer.py:42,66-71``, round wall-clock
``FedAVGAggregator.py:100-101,154``).  Here one sink owns all of it:

- ``MetricsLogger``: ``log(dict)`` → JSON-lines file + python logging
  + optional wandb, with the standard keys (round/epoch/spans).  A
  context manager with idempotent ``close()``; the record stream also
  carries the process-wide ``obs.telemetry`` registry (counter
  snapshots via ``log_telemetry``, compile/trace events drained as
  their own ``kind``-tagged records) so one ``metrics.jsonl`` is the
  whole story ``tools/trace_summary.py`` reads.
- ``span(name)``: context manager producing the same named spans as the
  reference (``time_aggregate``, ``time_round``, ...); each span also
  feeds the ``span.<name>_s`` telemetry histogram.
- ``trace(dir)``: ``jax.profiler`` trace context for TPU timelines,
  defaulting into the logger's ``run_dir``.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
from typing import Any, Dict, Optional

from fedml_tpu.obs.telemetry import Telemetry, get_telemetry

logger = logging.getLogger("fedml_tpu")


class MetricsLogger:
    def __init__(
        self,
        run_dir: Optional[str] = None,
        use_wandb: bool = False,
        wandb_kwargs: Optional[dict] = None,
        telemetry: Optional[Telemetry] = None,
        filename: str = "metrics.jsonl",
    ):
        self.run_dir = run_dir
        self.telemetry = telemetry or get_telemetry()
        self._fh = None
        if run_dir:
            os.makedirs(run_dir, exist_ok=True)
            # ``filename`` lets every federation PROCESS log into one
            # shared run_dir without interleaving: hub/server/clients
            # each append to their own metrics-node<id>.jsonl, and
            # tools/fed_timeline.py merges the set
            self._fh = open(os.path.join(run_dir, filename), "a")
        self._wandb = None
        if use_wandb:
            try:
                import wandb

                if wandb.run is None:
                    wandb.init(**(wandb_kwargs or {}))
                self._wandb = wandb
            except Exception:
                logger.warning("wandb requested but unavailable; file/log only")
        self.spans: Dict[str, float] = {}

    def _write(self, record: dict) -> None:
        # serialize once, and only when someone is listening: with no
        # JSONL file and logging above INFO this is a no-op, so the
        # always-on round instrumentation costs nothing in quiet runs
        if self._fh is None and not logger.isEnabledFor(logging.INFO):
            return
        line = json.dumps(record, default=float)
        logger.info("metrics %s", line)
        if self._fh:
            self._fh.write(line + "\n")
            self._fh.flush()

    def log(self, metrics: Dict[str, Any], step: Optional[int] = None) -> dict:
        record = dict(metrics)
        if step is not None:
            record.setdefault("round", step)
        # pending spans attach to ROUND rows only: an event record
        # (kind=trace/compile/...) logged mid-round must not steal the
        # in-flight time_* spans from the next round row
        if self.spans and "kind" not in record:
            record.update(self.pop_spans())
        record.setdefault("ts", time.time())  # fedlint: disable=determinism -- MetricsLogger IS the obs layer's writer (lives in core/ for import-order reasons); ts is record metadata
        self._write(record)
        if self._wandb:
            self._wandb.log(record, step=step)
        return record

    def log_telemetry(self) -> dict:
        """Merge the telemetry registry into the record stream: pending
        events (compile, trace, ...) become their own records,
        then one ``kind=telemetry`` snapshot of every counter / gauge /
        histogram is written.  Call at eval boundaries and at shutdown."""
        for ev in self.telemetry.drain_events():
            self._write(ev)
        record = {"kind": "telemetry", "ts": time.time(),  # fedlint: disable=determinism -- snapshot-record wall stamp (obs-role module); nothing replays it
                  **self.telemetry.snapshot()}
        self._write(record)
        return record

    def flush_events(self) -> int:
        """Drain pending telemetry events into the record stream WITHOUT
        the counter snapshot ``log_telemetry`` appends.  The registry's
        event ring is bounded (4096): a long traced federation run emits
        tens of ``trace_hop`` events per round, so an exit-time-only
        drain silently evicts the earliest chains — and the single
        ``clock_sync`` event, stamped at dial time, goes first, which
        would skew every stamp of that process in the merged timeline.
        Call this on a timer (``distributed_fedavg`` worker processes
        do) and keep ``log_telemetry`` for the final snapshot."""
        n = 0
        for ev in self.telemetry.drain_events():
            self._write(ev)
            n += 1
        return n

    @contextlib.contextmanager
    def span(self, name: str):
        """Named wall-clock span, attached to the next ``log`` call —
        the reference's manual time-logging pattern, centralized.
        Repeated spans of one name ACCUMULATE until popped (a round that
        packs twice reports the sum); each individual span additionally
        lands in the ``span.<name>_s`` telemetry histogram."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.spans[name] = self.spans.get(name, 0.0) + dt
            self.telemetry.observe(f"span.{name}_s", dt)

    def pop_spans(self) -> Dict[str, float]:
        """Pending spans as ``time_<name>`` keys; clears the accumulator."""
        out = {f"time_{k}": v for k, v in self.spans.items()}
        self.spans = {}
        return out

    def close(self) -> None:
        """Idempotent: safe to call twice, safe after ``with`` exit."""
        if self._fh:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None, logger: Optional[MetricsLogger] = None):
    """``jax.profiler`` trace context (open with TensorBoard/XProf).

    ``log_dir`` defaults to ``<logger.run_dir>/trace`` when a logger
    with a run_dir is given (so the trace lands next to metrics.jsonl),
    else ``/tmp/fedml_tpu_trace``; the chosen path is logged into the
    metrics stream so the run record points at its own trace.
    """
    import jax

    if log_dir is None:
        if logger is not None and logger.run_dir:
            log_dir = os.path.join(logger.run_dir, "trace")
        else:
            log_dir = "/tmp/fedml_tpu_trace"
    if logger is not None:
        logger.log({"kind": "trace", "trace_dir": log_dir})
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def setup_logging(rank: Optional[int] = None, level=logging.INFO) -> None:
    """Per-process format including the process rank — reference
    ``main_fedavg.py:286-289``."""
    tag = f"[rank {rank}] " if rank is not None else ""
    logging.basicConfig(
        level=level,
        format=f"%(asctime)s {tag}%(name)s %(levelname)s: %(message)s",
    )
