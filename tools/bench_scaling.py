"""Scaling-efficiency harness (BASELINE north star: >=90% efficiency
8 -> 256 client-chips; SURVEY.md §7.8).

Two modes, one JSON line per measured point:

- ``--mode chips`` (weak scaling across devices): fixed per-chip load,
  one FL client per chip on a ``clients`` mesh, D in a doubling ladder
  up to the available device count.  Efficiency_D = t_round(1) /
  t_round(D) — ideal 1.0 when aggregation rides the interconnect and
  the round stays compiled end-to-end.  On a TPU slice this measures
  ICI; under ``--platform cpu`` with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` it validates
  the harness + collective path without hardware.
- ``--mode clients`` (clients-per-chip scaling, runs on ONE chip): the
  packed client axis grows while per-client work is fixed; reports
  samples/s per point.  This is how a single v5e chip hosts many FL
  clients (sequential lax.map, full MXU tiles each).

Timing per point follows bench.py: warm until two consecutive
fully-synced calls agree, then median of synced per-call times.  In
chips mode one call == one round (dispatch-inclusive).  In clients mode
one call == ``--rounds-per-call`` rounds fused by ``make_multi_round_fn``
and ``s_per_round`` = call time / rounds_per_call — the per-dispatch
host round-trip is deliberately amortized out (PROFILE.md round 1
measured it at ~40% of per-round wall-clock), so the points report
compute scaling;
pass ``--rounds-per-call 1`` for dispatch-inclusive points.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _measure(round_fn, state, args_dev, rounds):
    from fedml_tpu.utils.timing import measure_rounds

    return measure_rounds(round_fn, state, args_dev, rounds)


def _make_inputs(C, S, B, shape, classes, seed=0):
    rng = np.random.RandomState(seed)
    return (
        rng.rand(C, S, B, *shape).astype(np.float32),
        rng.randint(0, classes, (C, S, B)).astype(np.int32),
        np.ones((C, S, B), np.float32),
        np.full((C,), S * B, np.float32),
        np.ones((C,), np.float32),
        np.arange(C, dtype=np.int32),
    )


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["chips", "clients"], default="clients")
    p.add_argument("--platform", default=None,
                   help="cpu to run on the faked host mesh")
    p.add_argument("--devices", type=int, default=8,
                   help="host devices to fake when --platform cpu")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument(
        "--rounds-per-call", type=int, default=5,
        help="clients mode: rounds fused per compiled call "
        "(make_multi_round_fn) so the point measures compute scaling, "
        "not per-dispatch host latency (PROFILE.md)",
    )
    p.add_argument("--model", default="resnet20",
                   help="resnet20 (cpu-friendly), resnet56, or mlp "
                   "(near-zero compile — CI harness validation)")
    args = p.parse_args()

    if args.platform == "cpu":
        # both are read once, when jax starts
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import (
        ServerState,
        resolve_compute_dtype,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models import resnet as resnet_mod

    if args.model == "mlp":
        # 8x8 inputs through a small MLP: the harness logic (meshes,
        # ladders, fused rounds, timing) without conv compile cost
        from fedml_tpu.models.linear import mlp2

        image = 8
        bundle = mlp2(image * image * 3, 32, 10, input_shape=(image, image, 3))
    else:
        image = 32 if args.model == "resnet56" else 16
        bundle = getattr(resnet_mod, args.model)(num_classes=10, image_size=image)
    opt = make_client_optimizer("sgd", 0.01, momentum=0.9)
    local_update = make_local_update(
        bundle, opt, epochs=1,
        compute_dtype=resolve_compute_dtype(
            "bf16" if args.platform != "cpu" else None
        ),
    )

    def fresh_state():
        key = jax.random.PRNGKey(0)
        return ServerState(
            variables=bundle.init(key), opt_state=(),
            round_idx=jnp.zeros((), jnp.int32), key=key,
        )

    S, B = args.steps, args.batch
    results = []
    if args.mode == "chips":
        from fedml_tpu.parallel.spmd import (
            make_client_mesh, make_spmd_round_fn, replicate,
            shard_client_block,
        )

        ladder, d = [], 1
        while d <= jax.device_count():
            ladder.append(d)
            d *= 2
        t1 = None
        for D in ladder:
            mesh = make_client_mesh(D)
            rf = make_spmd_round_fn(mesh, local_update, donate=False)
            inputs = shard_client_block(
                mesh, _make_inputs(D, S, B, (image, image, 3), 10)
            )
            t, _ = _measure(rf, replicate(mesh, fresh_state()), inputs,
                            args.rounds)
            t1 = t1 if t1 is not None else t
            point = {
                "metric": "weak_scaling_round_time",
                "devices": D, "clients": D, "value": round(t, 4),
                "unit": "s/round", "efficiency": round(t1 / t, 3),
            }
            if args.platform == "cpu" and (os.cpu_count() or 1) < D:
                # D faked devices time-share fewer physical cores: the
                # efficiency number measures the host, not the design
                point["note"] = (
                    f"{D} virtual devices on {os.cpu_count()} core(s) — "
                    "correctness/harness validation only"
                )
            results.append(point)
    else:
        from fedml_tpu.algorithms.fedavg import make_multi_round_fn

        rpc = args.rounds_per_call
        rf = jax.jit(make_multi_round_fn(local_update, rpc))
        for C in (1, 2, 4, 8, 16):
            inputs = tuple(
                jnp.asarray(a)
                for a in _make_inputs(C, S, B, (image, image, 3), 10)
            )
            t, _ = _measure(rf, fresh_state(), inputs, args.rounds)
            results.append({
                "metric": "clients_per_chip_throughput",
                "clients": C, "value": round(C * S * B * rpc / t, 1),
                "unit": "samples/sec", "s_per_round": round(t / rpc, 4),
                "rounds_per_call": rpc,
            })

    for r in results:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
