"""Which device a run is on — said once, by the code that measures.

``device_report()`` is what every result row carries (``bench.py``'s JSON
line, ``experiments/run.py``'s ``kind: config`` row, ``chip_smoke.py``).
``require_tpu()`` is what a measurement path calls before it builds
anything: with no chip JAX falls back to XLA:CPU without complaint, and a
number timed there must never be printed under a device metric's name.
"""

from __future__ import annotations


def device_report() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them for the default backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> list:
    """The default backend's devices; raises unless they are TPUs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"this path measures a TPU and found platform={platform!r} "
            f"({devices[0].device_kind}, {len(devices)} device(s)); it "
            "does not fall back to another backend"
        )
    return devices
