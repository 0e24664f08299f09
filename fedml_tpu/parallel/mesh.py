"""One dp×mp device mesh over the federation: cohort rows on ``dp``,
model tensors on ``mp``.

Every other mesh in ``parallel/`` is special-cased to its consumer —
``spmd.make_1d_mesh`` (clients axis for shard_map rounds),
``dp_sp.make_dp_sp_mesh`` (clients×sequence for ring attention).
This module is the user-facing knob: ONE ``--mesh dp,mp``
string parsed once and handed to the partition-rule engine
(``parallel/partition.py``), which lays the fedllm model over ``mp``
and the virtual-client cohort (the vmap axis of the PR-10 muxed
engine) over ``dp`` in the same jit step.

CPU host-mesh howto (no accelerator required): set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE the
first jax import and the host platform exposes 8 CpuDevices — enough
to pin sharded-vs-replicated byte identity (``tests/test_shard_rules``)
and exercise every collective the partitioner inserts.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

DP_AXIS = "dp"
MP_AXIS = "mp"

HOST_MESH_HINT = (
    "set XLA_FLAGS=--xla_force_host_platform_device_count=<n> before "
    "the first jax import to expose n host devices"
)


def parse_mesh_spec(
    spec: str, device_count: Optional[int] = None
) -> Tuple[int, int]:
    """Parse ``--mesh`` strings into ``(dp, mp)``.

    Accepted forms: ``"4,2"``, ``"dp=4,mp=2"`` (order-free), and
    ``"auto,2"`` / ``"-1,2"`` where the auto dimension absorbs every
    device the other doesn't claim.  At most one dimension may be
    auto.  ``device_count=None`` defers to ``jax.device_count()``.
    """
    parts = [p.strip() for p in str(spec).split(",") if p.strip()]
    if len(parts) != 2:
        raise ValueError(
            f"mesh spec {spec!r} must have exactly two dimensions "
            "(dp,mp), e.g. '8,1' or 'dp=8,mp=1'"
        )
    dims = {}
    for i, part in enumerate(parts):
        name = (DP_AXIS, MP_AXIS)[i]
        if "=" in part:
            name, _, part = part.partition("=")
            name = name.strip()
            part = part.strip()
            if name not in (DP_AXIS, MP_AXIS):
                raise ValueError(
                    f"mesh spec {spec!r}: unknown axis {name!r} "
                    f"(want {DP_AXIS}/{MP_AXIS})"
                )
        if name in dims:
            raise ValueError(f"mesh spec {spec!r} names {name!r} twice")
        if part in ("auto", "-1"):
            dims[name] = -1
        else:
            try:
                dims[name] = int(part)
            except ValueError:
                raise ValueError(
                    f"mesh spec {spec!r}: dimension {part!r} is not an "
                    "integer (or 'auto')"
                ) from None
    if DP_AXIS not in dims or MP_AXIS not in dims:
        raise ValueError(
            f"mesh spec {spec!r} must name both {DP_AXIS} and {MP_AXIS}"
        )
    dp, mp = dims[DP_AXIS], dims[MP_AXIS]
    if dp == -1 and mp == -1:
        raise ValueError(f"mesh spec {spec!r}: only one axis may be auto")
    if dp == -1 or mp == -1:
        if device_count is None:
            import jax

            device_count = jax.device_count()
        fixed = mp if dp == -1 else dp
        if fixed <= 0 or device_count % fixed:
            raise ValueError(
                f"mesh spec {spec!r}: {device_count} devices not "
                f"divisible by fixed axis {fixed}"
            )
        auto = device_count // fixed
        dp, mp = (auto, mp) if dp == -1 else (dp, auto)
    if dp <= 0 or mp <= 0:
        raise ValueError(f"mesh spec {spec!r}: axes must be positive")
    return dp, mp


def device_grid(shape: Sequence[int], devices: Optional[Sequence] = None):
    """The first ``prod(shape)`` devices arranged as ``shape`` — the one
    place every mesh constructor in ``parallel/`` gets its device array.

    On a TPU the arrangement follows the host's physical topology
    (``mesh_utils.create_device_mesh``: on a 2x2 v5e host a 1-D axis
    becomes the ring 0-1-3-2, so neighbours on the axis are neighbours
    on the interconnect); ``jax.devices()`` list order does not.  On
    other platforms it is list order.  Raises — with the host-mesh
    hint — when the platform doesn't have enough devices."""
    import jax
    from jax.experimental import mesh_utils

    devices = list(devices) if devices is not None else jax.devices()
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh {'x'.join(map(str, shape))} needs {n} devices, have "
            f"{len(devices)} ({HOST_MESH_HINT})"
        )
    return mesh_utils.create_device_mesh(tuple(shape), devices[:n])


def make_dp_mp_mesh(dp: int, mp: int, *, devices: Optional[Sequence] = None):
    """A ``Mesh`` with axes ``("dp", "mp")`` over the first dp*mp
    devices (``device_grid``)."""
    from jax.sharding import Mesh

    return Mesh(device_grid((dp, mp), devices),
                axis_names=(DP_AXIS, MP_AXIS))


def mesh_from_spec(spec: str, *, devices: Optional[Sequence] = None):
    """``parse_mesh_spec`` + ``make_dp_mp_mesh`` in one call."""
    count = len(devices) if devices is not None else None
    dp, mp = parse_mesh_spec(spec, device_count=count)
    return make_dp_mp_mesh(dp, mp, devices=devices)


def describe_mesh(mesh) -> dict:
    """JSON-friendly summary for evidence files and logs."""
    return {
        "axes": {name: int(size) for name, size in mesh.shape.items()},
        "devices": int(mesh.devices.size),
        "platform": str(mesh.devices.flat[0].platform),
    }
