#!/usr/bin/env python
"""FEDSHARD campaign driver (PR 19): the partition-rule sharding
engine's evidence file, ``FEDSHARD_r19.json``.

Five arms:

1. **Rule coverage** — the canonical tables (``fedllm``, ``resnet``)
   matched against their real model families: per-rule leaf/param
   counts, zero unmatched paths, every rule earning its keep (>= 1
   leaf).
2. **Digest pins, in-process** — the rule-driven round engine
   (``partition.make_rule_round_fn``) on host meshes dp in {1, 2, 8}
   (mp=1) vs the plain single-device engine, fp32 AND int8+EF: the
   final global model sha256 must be IDENTICAL across every cell.
   Each cell is a subprocess because
   ``--xla_force_host_platform_device_count`` must be set before jax
   initializes.  An mp=2 cell runs as allclose only — mp splits the
   matmul contraction dim, which reassociates fp32 reductions by
   construction (bit-parity over mp is not a claim this engine makes).
3. **Muxed pin** — the full federation (``distributed_fedavg.launch``)
   per-process vs muxed-on-host-mesh (``--mesh 4,1``): every client
   upload digest and every final-model leaf byte-identical.
4. **Per-shard wire bytes** — ``compress.sharded.wire_encode_tree_sharded``
   on a dp2 x mp2 mesh: each shard's packed buffers byte-identical to a
   single-device encode of that shard's slice under the same
   ``fold_in(fold_in(key, leaf), shard)`` stream, shard elements summing
   exactly to leaf elements (each element visited once — no gather, no
   overlap), decode roundtrip equal to the plain codec roundtrip.
5. **Cohort throughput** — the 256-virtual-client point, dp=1 vs dp=8
   host mesh, p50 round wall.  Target 2x; on this 1-core box host
   "devices" are threads multiplexed onto one core, so the bar is
   expected to MISS here and is reported honestly with the chip-sweep
   command deferred to PROFILE.md (the FEDXPORT_r13 precedent).

``ok`` is the AND of arms 1-4; arm 5 records ``met`` in its own
section.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_VOCAB = 64
_EMBED = 32
_HEADS = 2
_LAYERS = 1
_SEQ = 16


def _child_env(devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        if devices > 1 else ""
    )
    return env


def _synthetic(seed: int, clients: int, steps: int, batch: int):
    """Deterministic host-side token data, identical in every child:
    x [K, steps, B, L] tokens, y next-token targets, mask ones."""
    import numpy as np

    rng = np.random.default_rng(seed)
    toks = rng.integers(
        0, _VOCAB, size=(clients, steps, batch, _SEQ + 1), dtype=np.int64
    )
    x = toks[..., :-1].astype(np.int32)
    y = toks[..., 1:].astype(np.int32)
    mask = np.ones((clients, steps, batch), np.float32)
    num_samples = np.full((clients,), steps * batch, np.float32)
    participation = np.ones((clients,), np.float32)
    slot_ids = np.arange(clients, dtype=np.int32)
    return x, y, mask, num_samples, participation, slot_ids


def _model_and_update(epochs: int = 1):
    import jax

    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.transformer import transformer_lm

    bundle = transformer_lm(
        vocab_size=_VOCAB, embed_dim=_EMBED, num_heads=_HEADS,
        num_layers=_LAYERS, seq_len=_SEQ,
    )
    opt = make_client_optimizer("sgd", 0.1)
    lu = make_local_update(bundle, opt, epochs=epochs)
    variables = bundle.init(jax.random.PRNGKey(0))
    return bundle, lu, variables


def _tree_digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in sorted(
        jax.tree_util.tree_leaves_with_path(tree),
        key=lambda kv: jax.tree_util.keystr(kv[0]),
    ):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


# --- child payloads (run under a fresh XLA_FLAGS) ---------------------------

def child_pin(args) -> dict:
    """One digest cell: rounds of the rule engine (or the plain
    single-device engine) over the shared synthetic federation; prints
    the final-model sha256."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
    from fedml_tpu.compress import get_codec
    from fedml_tpu.parallel.mesh import make_dp_mp_mesh
    from fedml_tpu.parallel.partition import FEDLLM_RULES, make_rule_round_fn

    clients, rounds = args.clients, args.rounds
    _, lu, variables = _model_and_update()
    codec = get_codec(args.codec or None)
    ef = bool(args.ef) and codec is not None
    residuals = ()
    if ef:
        residuals = jax.tree_util.tree_map(
            lambda l: jnp.zeros((clients,) + l.shape, jnp.float32),
            variables,
        )
    state = ServerState(
        variables=variables, opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(args.seed),
        residuals=residuals,
    )
    data = _synthetic(args.seed, clients, steps=2, batch=2)
    if args.engine == "rules":
        mesh = make_dp_mp_mesh(args.dp, args.mp)
        round_fn, shard_state, shard_data = make_rule_round_fn(
            mesh, lu, variables, FEDLLM_RULES,
            codec=codec, error_feedback=ef,
        )
        state = shard_state(state)
    else:
        inner = make_round_fn(
            lu, client_axis_impl="vmap", codec=codec, error_feedback=ef,
        )
        round_fn = jax.jit(inner, donate_argnums=(0,))

        def shard_data(arrays):
            return tuple(jnp.asarray(a) for a in arrays)

    losses = []
    for _ in range(rounds):
        state, m = round_fn(state, *shard_data(data))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    return {
        "engine": args.engine, "dp": args.dp, "mp": args.mp,
        "codec": args.codec or "fp32", "ef": bool(ef), "rounds": rounds,
        "devices": jax.device_count(),
        "digest": _tree_digest(state.variables),
        "losses": [round(v, 6) for v in losses],
        "nan_free": all(v == v for v in losses),
    }


def child_bytes(args) -> dict:
    """Per-shard wire-byte identity on a dp x mp mesh: every shard's
    packed buffers vs a single-device encode of the same slice, plus
    exact element accounting and decode-roundtrip equality."""
    import jax
    import numpy as np

    from fedml_tpu.compress import get_codec
    from fedml_tpu.compress.codecs import (
        _leaf_keys, wire_encode_tree,
    )
    from fedml_tpu.compress.sharded import (
        sharded_entry_nbytes, sharded_wire_digest, shard_slices,
        wire_decode_tree_sharded, wire_encode_tree_sharded,
    )
    from fedml_tpu.parallel.mesh import make_dp_mp_mesh
    from fedml_tpu.parallel.partition import FEDLLM_RULES, shard_by_rules

    codec = get_codec(args.codec)
    _, _, variables = _model_and_update()
    mesh = make_dp_mp_mesh(args.dp, args.mp)
    sharded, _specs = shard_by_rules(mesh, variables, FEDLLM_RULES)
    key = jax.random.PRNGKey(args.seed)
    entries = wire_encode_tree_sharded(codec, sharded, key)

    leaves = jax.tree_util.tree_leaves(sharded)
    host_leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(variables)]
    shard_match = element_match = True
    total_shards = 0
    multi_shard_leaves = 0
    wire_bytes = 0
    for i, (leaf, full, entry) in enumerate(
        zip(leaves, host_leaves, entries)
    ):
        k_leaf = list(_leaf_keys(key, len(leaves)))[i]
        slices = shard_slices(leaf)
        if len(slices) > 1:
            multi_shard_leaves += 1
        elems = 0
        for j, ((bounds, _data), sh) in enumerate(zip(slices, entry["shards"])):
            total_shards += 1
            sel = tuple(slice(lo, hi) for lo, hi in bounds)
            elems += int(np.prod([hi - lo for lo, hi in bounds]))
            ref = codec.wire_pack({
                name: np.asarray(v)
                for name, v in codec.encode(
                    np.asarray(full[sel]), jax.random.fold_in(k_leaf, j)
                ).items()
            })
            for name in sorted(set(ref) | set(sh["enc"])):
                a = np.asarray(ref.get(name))
                b = np.asarray(sh["enc"].get(name))
                if a.shape != b.shape or not np.array_equal(a, b):
                    shard_match = False
        if elems != int(np.prod(np.shape(full), dtype=np.int64)):
            element_match = False
        wire_bytes += sum(sharded_entry_nbytes(entry))

    decoded = wire_decode_tree_sharded(codec, entries, variables)
    plain_entries = wire_encode_tree(codec, variables, key)
    plain_bytes = sum(
        int(np.asarray(v).nbytes)
        for e in plain_entries for v in e["enc"].values()
    )
    finite = all(
        bool(np.isfinite(l).all()) for l in jax.tree_util.tree_leaves(decoded)
    )
    return {
        "codec": args.codec, "dp": args.dp, "mp": args.mp,
        "devices": jax.device_count(),
        "leaves": len(leaves),
        "multi_shard_leaves": multi_shard_leaves,
        "shards_total": total_shards,
        "per_shard_bytes_identical": bool(shard_match),
        "element_accounting_exact": bool(element_match),
        "decode_finite": finite,
        "wire_bytes_sharded": int(wire_bytes),
        "wire_bytes_plain": int(plain_bytes),
        "sharded_wire_digest": sharded_wire_digest(entries),
    }


def child_throughput(args) -> dict:
    """The 256-virtual-client cohort point: p50 round wall of the rule
    engine on this mesh width (first round = jit warmup, excluded)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState
    from fedml_tpu.parallel.mesh import make_dp_mp_mesh
    from fedml_tpu.parallel.partition import FEDLLM_RULES, make_rule_round_fn

    clients, rounds = args.clients, args.rounds
    _, lu, variables = _model_and_update()
    state = ServerState(
        variables=variables, opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(args.seed),
    )
    mesh = make_dp_mp_mesh(args.dp, args.mp)
    round_fn, shard_state, shard_data = make_rule_round_fn(
        mesh, lu, variables, FEDLLM_RULES,
    )
    state = shard_state(state)
    data = shard_data(_synthetic(args.seed, clients, steps=1, batch=2))
    samples = []
    for r in range(rounds + 1):
        t0 = time.perf_counter()
        state, m = round_fn(state, *data)
        jax.block_until_ready(m["loss_sum"])
        if r:  # round 0 is compile
            samples.append(time.perf_counter() - t0)
    samples.sort()
    return {
        "dp": args.dp, "mp": args.mp, "clients": clients,
        "devices": jax.device_count(), "rounds_timed": rounds,
        "round_wall_s": [round(s, 4) for s in samples],
        "p50_s": round(samples[len(samples) // 2], 4),
    }


def _spawn(child: str, devices: int, timeout: float, **kw) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", child]
    for k, v in kw.items():
        cmd += [f"--{k.replace('_', '-')}", str(v)]
    out = subprocess.run(
        cmd, env=_child_env(devices), capture_output=True, text=True,
        timeout=timeout, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"child {child} {kw} failed rc={out.returncode}:\n"
            f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.splitlines()[-1])


# --- parent arms ------------------------------------------------------------

def run_coverage() -> dict:
    import jax

    from fedml_tpu.parallel.partition import (
        FEDLLM_RULES, RESNET_RULES, rule_coverage,
    )

    from fedml_tpu.models.resnet import resnet20

    _, _, tvars = _model_and_update()
    out = {"fedllm": rule_coverage(FEDLLM_RULES, tvars)}
    rvars = resnet20(num_classes=10).init(jax.random.PRNGKey(0))
    out["resnet"] = rule_coverage(RESNET_RULES, rvars)
    ok = True
    for name, cov in out.items():
        if cov["unmatched_paths"]:
            ok = False
        if any(r["leaves"] == 0 for r in cov["rules"]):
            ok = False
    out["ok"] = ok
    return out


def run_pins(args) -> dict:
    cells = []
    matrix = [
        ("plain", 1, 1, "", 0),
        ("rules", 1, 1, "", 0),
        ("rules", 2, 1, "", 0),
        ("rules", 8, 1, "", 0),
        ("plain", 1, 1, "int8", 1),
        ("rules", 1, 1, "int8", 1),
        ("rules", 2, 1, "int8", 1),
        ("rules", 8, 1, "int8", 1),
    ]
    for engine, dp, mp, codec, ef in matrix:
        cells.append(_spawn(
            "pin", devices=dp * mp, timeout=args.timeout,
            engine=engine, dp=dp, mp=mp, codec=codec, ef=ef,
            clients=args.pin_clients, rounds=args.pin_rounds, seed=args.seed,
        ))
    by_codec = {}
    for c in cells:
        by_codec.setdefault((c["codec"], c["ef"]), []).append(c)
    identical = {
        f"{codec}_ef{int(ef)}": len({c["digest"] for c in group}) == 1
        for (codec, ef), group in by_codec.items()
    }
    # mp=2 reassociates the contraction dim: allclose-only cell
    mp2 = _spawn(
        "pin", devices=8, timeout=args.timeout,
        engine="rules", dp=4, mp=2, codec="", ef=0,
        clients=args.pin_clients, rounds=args.pin_rounds, seed=args.seed,
    )
    ref = next(c for c in cells if c["engine"] == "plain" and not c["ef"])
    mp2_close = all(
        abs(a - b) < 1e-3
        for a, b in zip(mp2["losses"], ref["losses"])
    )
    ok = (all(identical.values()) and all(c["nan_free"] for c in cells)
          and mp2["nan_free"] and mp2_close)
    return {
        "cells": cells,
        "identical_within_codec": identical,
        "mp2_cell": {**mp2, "losses_allclose_vs_plain": mp2_close},
        "ok": ok,
    }


def run_mux_pin(args) -> dict:
    import numpy as np

    from fedml_tpu.experiments.distributed_fedavg import launch

    results = {}
    with tempfile.TemporaryDirectory() as td:
        for tag, kw, devices in (
            ("per_process", dict(muxers=0), 1),
            ("muxed_mesh", dict(muxers=1, muxed_clients=args.mux_clients,
                                mesh="4,1"), 4),
        ):
            out = os.path.join(td, f"{tag}.npz")
            info = {}
            rc = launch(
                num_clients=args.mux_clients, rounds=args.mux_rounds,
                seed=args.seed, batch_size=16, out_path=out,
                env=_child_env(devices),
                info=info, timeout=args.timeout, **kw,
            )
            z = np.load(out)
            results[tag] = {
                "rc": rc,
                "digests": {k: v for k, v in sorted(info.items())
                            if k.endswith("_upload_digest")},
                "leaves": [np.asarray(z[k]) for k in sorted(z.files)
                           if k.startswith("leaf_")],
            }
    a, b = results["per_process"], results["muxed_mesh"]
    digests_ok = a["digests"] == b["digests"] and len(a["digests"]) > 0
    model_ok = len(a["leaves"]) == len(b["leaves"]) and all(
        np.array_equal(x, y) for x, y in zip(a["leaves"], b["leaves"])
    )
    return {
        "clients": args.mux_clients, "rounds": args.mux_rounds,
        "mesh": "4,1",
        "rc": {t: r["rc"] for t, r in results.items()},
        "digests": a["digests"],
        "digests_identical": digests_ok,
        "final_model_identical": bool(model_ok),
        "ok": bool(a["rc"] == 0 and b["rc"] == 0 and digests_ok and model_ok),
    }


def run_bytes(args) -> dict:
    out = {}
    ok = True
    for codec in ("int8", "int4"):
        cell = _spawn(
            "bytes", devices=4, timeout=args.timeout,
            codec=codec, dp=2, mp=2, seed=args.seed,
        )
        out[codec] = cell
        ok = ok and cell["per_shard_bytes_identical"] \
            and cell["element_accounting_exact"] and cell["decode_finite"] \
            and cell["multi_shard_leaves"] > 0
    out["ok"] = ok
    return out


def run_throughput(args) -> dict:
    arms = {}
    for dp in (1, 8):
        arms[f"dp{dp}"] = _spawn(
            "throughput", devices=dp, timeout=args.timeout,
            dp=dp, mp=1, clients=args.tp_clients, rounds=args.tp_rounds,
            seed=args.seed,
        )
    speedup = arms["dp1"]["p50_s"] / max(arms["dp8"]["p50_s"], 1e-9)
    met = speedup >= args.tp_target
    return {
        "arms": arms,
        "target_speedup": args.tp_target,
        "speedup": round(speedup, 3),
        "met": bool(met),
        "note": (
            "host-mesh devices on this box are threads multiplexed onto "
            "nproc=1 core — dp width adds partition overhead without "
            "parallel compute, so the 2x bar cannot be met here; the "
            "real-chip sweep command is recorded in PROFILE.md (r19 "
            "appendix), same deferral shape as FEDXPORT_r13's chip bars"
        ) if not met else "",
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", choices=("pin", "bytes", "throughput"))
    ap.add_argument("--engine", default="rules")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--codec", default="")
    ap.add_argument("--ef", type=int, default=0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pin-clients", type=int, default=16)
    ap.add_argument("--pin-rounds", type=int, default=3)
    ap.add_argument("--mux-clients", type=int, default=8)
    ap.add_argument("--mux-rounds", type=int, default=2)
    ap.add_argument("--tp-clients", type=int, default=256)
    ap.add_argument("--tp-rounds", type=int, default=5)
    ap.add_argument("--tp-target", type=float, default=2.0)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--skip-throughput", action="store_true")
    ap.add_argument("--out", default="FEDSHARD_r19.json")
    args = ap.parse_args()

    if args.child:
        fn = {"pin": child_pin, "bytes": child_bytes,
              "throughput": child_throughput}[args.child]
        print(json.dumps(fn(args)))
        return 0

    doc = {
        "experiment": (
            "partition-rule sharding engine: ordered (regex -> "
            "PartitionSpec) tables over one dp x mp mesh covering the "
            "fedllm model AND the virtual-client cohort, with per-shard "
            "QSGD wire encode and bit-exact dp aggregation"
        ),
        "generated_unix": round(time.time(), 1),
    }
    t0 = time.time()
    doc["coverage"] = run_coverage()
    print(f"[coverage] ok={doc['coverage']['ok']}", flush=True)
    doc["digest_pins"] = run_pins(args)
    print(f"[digest_pins] ok={doc['digest_pins']['ok']} "
          f"{doc['digest_pins']['identical_within_codec']}", flush=True)
    doc["mux_pin"] = run_mux_pin(args)
    print(f"[mux_pin] ok={doc['mux_pin']['ok']}", flush=True)
    doc["shard_bytes"] = run_bytes(args)
    print(f"[shard_bytes] ok={doc['shard_bytes']['ok']}", flush=True)
    if not args.skip_throughput:
        doc["throughput_256"] = run_throughput(args)
        print(f"[throughput_256] speedup="
              f"{doc['throughput_256']['speedup']} "
              f"met={doc['throughput_256']['met']}", flush=True)
    doc["wall_s"] = round(time.time() - t0, 1)
    doc["ok"] = all(doc[k]["ok"] for k in
                    ("coverage", "digest_pins", "mux_pin", "shard_bytes"))
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
    print(f"wrote {args.out} ok={doc['ok']}")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
