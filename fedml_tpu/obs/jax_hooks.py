"""JAX runtime hooks: compile tracking and memory gauges.

Two observability gaps this closes (ISSUE 1):

- **Recompile storms are invisible.**  ``instrument_jit`` wraps a jitted
  callable and tracks the abstract signature (treedef + shape/dtype per
  leaf, python scalars by weak type) of every call; the first call under
  a new signature is counted as a compile event with its wall seconds.
  A round driver that accidentally varies a shape per round shows up as
  ``jax.compiles{fn=round_fn}`` climbing with the round index instead of
  sitting at 1-2.  All bookkeeping is host-side dict lookups — nothing
  is added inside the traced function.
- **Device memory pressure is invisible.**  ``record_device_memory``
  snapshots ``Device.memory_stats()`` (``None`` on the CPU backend, which
  is skipped) into high-water gauges.

``install_jax_monitoring`` additionally subscribes to
``jax.monitoring`` duration events (event names containing "compile"),
which yields the backend's OWN compile seconds where available —
``instrument_jit``'s triggering-call wall time is an upper bound that
includes dispatch.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Tuple

from fedml_tpu.obs.telemetry import Telemetry, get_telemetry

_MONITORING_INSTALLED = False


def abstract_signature(args, kwargs=None) -> Optional[Tuple]:
    """Hashable jit-specialization key: treedef + per-leaf (shape, dtype)
    for arrays; python scalars by TYPE only — jit weak-types a plain
    int/float to one dtype regardless of value, so keying on the value
    would report a fake compile on every varying-scalar call (the exact
    false recompile-storm this layer exists to detect)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        elif isinstance(leaf, (bool, int, float, complex)):
            sig.append((type(leaf).__name__,))
        else:
            try:
                hash(leaf)
            except TypeError:
                return None  # unhashable static leaf: skip tracking this call
            sig.append((type(leaf).__name__, leaf))
    return (treedef, tuple(sig))


def instrument_jit(fn: Callable, name: str,
                   telemetry: Optional[Telemetry] = None) -> Callable:
    """Wrap a jitted callable with host-side compile-event tracking.

    Per NEW signature: ``jax.compiles{fn=name}`` += 1, the triggering
    call's wall seconds land in ``jax.compile_s{fn=name}`` and a
    ``compile`` event (drained into metrics.jsonl by
    ``MetricsLogger.log_telemetry``).  Warm calls pay one tree_flatten +
    dict probe (~µs) — never anything inside the traced code.
    """
    seen: dict = {}

    def wrapped(*args, **kwargs):
        t = telemetry or get_telemetry()
        sig = abstract_signature(args, kwargs)
        if sig is None or sig in seen:
            return fn(*args, **kwargs)
        seen[sig] = len(seen)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        t.inc("jax.compiles", 1, fn=name)
        t.observe("jax.compile_s", dt, fn=name)
        t.event("compile", fn=name, signature=seen[sig],
                n_signatures=len(seen), seconds=round(dt, 6))
        return out

    wrapped.__name__ = f"instrumented[{name}]"
    wrapped.__wrapped__ = fn
    return wrapped


def install_jax_monitoring(telemetry: Optional[Telemetry] = None) -> bool:
    """Subscribe compile-duration events from ``jax.monitoring`` into the
    registry (idempotent)."""
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return True
    from jax import monitoring

    def _on_duration(event, duration, **kw):
        if "compile" not in event:
            return
        t = telemetry or get_telemetry()
        t.inc("jax.backend_compile_events", 1, event=event)
        try:
            t.observe("jax.backend_compile_s", float(duration), event=event)
        except ValueError:
            pass  # non-finite duration from the runtime: drop, don't raise

    monitoring.register_event_duration_secs_listener(_on_duration)
    _MONITORING_INSTALLED = True
    return True


def record_device_memory(telemetry: Optional[Telemetry] = None) -> dict:
    """Snapshot per-device memory into gauges; returns {device: stats}.

    ``jax.device_mem_peak_bytes{device=...}`` is a high-water gauge
    (max over all snapshots this process); ``jax.device_mem_bytes`` is
    the point-in-time residency.  Backends without ``memory_stats``
    (CPU) contribute nothing.
    """
    import jax

    t = telemetry or get_telemetry()
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats()  # None on backends that keep none (CPU)
        if not stats:
            continue
        out[d.id] = stats
        peak = stats.get("peak_bytes_in_use")
        in_use = stats.get("bytes_in_use")
        if peak is not None:
            t.gauge_max("jax.device_mem_peak_bytes", peak, device=d.id)
        if in_use is not None:
            t.gauge_set("jax.device_mem_bytes", in_use, device=d.id)
    return out
