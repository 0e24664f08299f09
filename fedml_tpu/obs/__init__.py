"""Observability layer: process-wide telemetry + JAX/comm hooks.

Modules (kept import-light on purpose — ``telemetry`` is pure stdlib so
core/comm can depend on it without cycles or jax import cost):

- ``telemetry``  — counters / gauges / log-bucketed histograms with a
  JSONL-able snapshot, the process-wide registry every layer reports to;
- ``comm_obs``   — per-message-type send/recv counters for the comm
  backends (wired into ``CommBackend``, so transports and algorithms
  need no changes to be measured);
- ``jax_hooks``  — compile-event tracking per jit signature, device
  memory high-water gauges;
- ``digest``     — mergeable registry digests (the in-band stats plane:
  associative ``merge``, delta sources, the server-side rollup);
- ``slo``        — the declarative federation SLO engine + the atomic
  ``status.json`` / ``slo_report.json`` writers.

NOTE: do not import ``jax_hooks`` here — ``core.metrics`` imports
``obs.telemetry`` (which executes this file), and ``jax_hooks`` imports
``core.metrics`` lazily; a top-level import would close that cycle.
"""

from fedml_tpu.obs.telemetry import (
    Telemetry,
    get_telemetry,
    metric_key,
    parse_metric_key,
)

__all__ = ["Telemetry", "get_telemetry", "metric_key", "parse_metric_key"]
