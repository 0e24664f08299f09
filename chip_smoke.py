#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # on a machine with a TPU
    python chip_smoke.py --cpu-rehearsal  # toy sizes on the CPU, to shake
                                          # the script out before a chip run

Drives the main path once through the entry points a user would call, at
the full width of the models the repo supports, and checks what comes out
by the repo's own means.  The legs, one child process each, one after
another (a chip belongs to one process at a time, and this parent never
imports JAX — the federation leg must itself start a child that owns the
chip):

  resnet56_run     python -m fedml_tpu.experiments.run --algorithm fedavg
                   --model resnet56 --dataset cifar10 --compute_dtype bf16,
                   10 clients all participating (per-round dispatch,
                   FedAvgSimulation.run, eval, metrics.jsonl, memory gauges)
  resnet56_fused   make_local_update -> make_multi_round_fn at bench.py's
                   geometry (10 clients x 24 steps x 64): warm-up + 2 calls
  fedllm_fused     the transformer at width 1280, 12 layers, 10 heads,
                   L = 1024, vocab 8192, 4 clients x 4 steps x batch 8
                   (bench.py --workload fedllm): warm-up + 1 call
  flash_attention  ops/flash_attention.py compiled through the model's
                   policy, forward and backward kernel: L = 1024 at
                   H = 20, D = 64 (the benchmark cells' shape), L = 2048
                   and 8192 at D = 128, against blockwise_attention at
                   "highest" matmul precision
  conv_mxu         ops/conv_mxu.py compiled, forward with and without
                   moments at ResNet-56's 3x3 shapes, against the XLA conv
  federation_mux   launch(num_clients=8, muxers=1, muxed_clients=8,
                   rounds=2, codec="int8"): hub, server and muxer as OS
                   processes, the muxer's vmapped cohort step on the chip
  resnet56_spmd4   ResNet-56 through make_spmd_round_fn on a 4-wide clients
                   mesh, cohort of 8 (needs four chips; otherwise
                   "not run: <n> device")

Every leg checks its own output (finite, falling losses; round_idx
advanced; peak device memory reported and non-zero; ZERO compilations
after the warm-up) and any failed leg makes the exit code non-zero.  The
compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to
<repo>/.jax_cache (fedml_tpu/utils/compile_cache.py); run it twice against
the same directory to see compile seconds cold and warm.

Without a TPU (and without --cpu-rehearsal) it exits non-zero, names the
platform it found and prints no result.  Any rate it prints is an
observation, not a baseline and not a claim.  Last line on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_MARK = "CHIP_SMOKE_RESULT "
REHEARSAL_TAG = "platform=cpu rehearsal"

# (leg, the most seconds it may take).  All of them together get
# TOTAL_BUDGET_S, which stays under the contract's 1200 s.
LEGS = (
    ("resnet56_run", 300),
    ("resnet56_fused", 420),
    ("fedllm_fused", 300),
    ("flash_attention", 200),
    ("conv_mxu", 120),
    ("federation_mux", 200),
    ("resnet56_spmd4", 420),
)
TOTAL_BUDGET_S = 1150


def run_dir(leg: str) -> str:
    """A fresh directory for a leg's files (metrics.jsonl appends)."""
    path = os.path.join(REPO, "runs", "chip_smoke", leg)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


# ---------------------------------------------------------------------------
# child side: one leg per process
# ---------------------------------------------------------------------------


class Watch:
    """Counts what this process compiles and which Pallas kernels it traces.

    Compilations are the backend's own events (``jax.monitoring``
    ``backend_compile_duration`` fires once per XLA program, whether it
    was compiled or loaded from the persistent cache); Pallas kernels are
    seen at trace time through ``pl.pallas_call``, with the ``interpret``
    flag they were given."""

    def __init__(self):
        from jax import monitoring
        from jax.experimental import pallas as pl

        self.compiles = []  # (wall time, seconds)
        self.cache = {"hits": 0, "misses": 0}
        self.kernels = []  # (kernel name, interpret)

        def on_duration(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.compiles.append((time.time(), float(duration)))

        def on_event(event, **_):
            if event.endswith("compilation_cache/cache_hits"):
                self.cache["hits"] += 1
            elif event.endswith("compilation_cache/cache_misses"):
                self.cache["misses"] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

        real = pl.pallas_call

        def recording_pallas_call(kernel, *args, **kwargs):
            name = getattr(kernel, "__name__", None) or getattr(
                getattr(kernel, "func", None), "__name__", repr(kernel))
            self.kernels.append((name, bool(kwargs.get("interpret", False))))
            return real(kernel, *args, **kwargs)

        pl.pallas_call = recording_pallas_call

    def compiles_since(self, t: float) -> int:
        return sum(1 for ts, _ in self.compiles if ts > t)

    def compile_seconds(self) -> float:
        return round(sum(s for _, s in self.compiles), 2)

    def kernel_summary(self) -> dict:
        out = {}
        for name, interp in self.kernels:
            key = f"{name}[{'interpreted' if interp else 'compiled'}]"
            out[key] = out.get(key, 0) + 1
        return out


def start_leg() -> Watch:
    """Common start of every JAX leg: compile cache, then the watch."""
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    return Watch()


def check(cond, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def max_rel_error(got, want) -> float:
    """max|got - want| as a fraction of max|want|; ``got`` must be finite."""
    import numpy as np

    got = np.asarray(got, np.float32)
    check(np.isfinite(got).all(), "non-finite values")
    return float(np.abs(got - want).max() / np.abs(want).max())


def peak_device_bytes(rehearsal: bool):
    """Largest ``peak_bytes_in_use`` over the devices, as the devices
    report it; on the chip it must be there and non-zero."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if s]
    if rehearsal and not peaks:
        return None  # the CPU backend keeps no statistics
    check(len(peaks) == len(stats) and min(peaks) > 0,
          f"device memory statistics missing or zero: {stats}")
    return max(peaks)


def leg_probe(rehearsal: bool) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from fedml_tpu.native import native_status
    from fedml_tpu.utils.compile_cache import configure_compile_cache
    from fedml_tpu.utils.device import device_report

    out = device_report()
    out.update(jax=jax.__version__, jaxlib=jaxlib.__version__,
               libtpu=metadata.version("libtpu"),
               compile_cache=configure_compile_cache(),
               gxx=shutil.which("g++") or "missing",
               packer=native_status())
    return out


def leg_resnet56_run(rehearsal: bool) -> dict:
    """Route 1: the normal entry point, per-round dispatch."""
    watch = start_leg()
    from fedml_tpu.experiments import run as run_mod

    rdir = run_dir("resnet56_run")
    argv = ["--algorithm", "fedavg", "--dataset", "cifar10",
            "--compute_dtype", "bf16", "--run_dir", rdir]
    if rehearsal:
        rounds = 3
        argv += ["--model", "resnet20", "--client_num_in_total", "3",
                 "--client_num_per_round", "3", "--batch_size", "8",
                 "--max_samples_per_client", "32",
                 "--max_test_samples", "32"]
    else:
        rounds = 4
        argv += ["--model", "resnet56", "--client_num_in_total", "10",
                 "--client_num_per_round", "10"]
    argv += ["--comm_round", str(rounds)]
    out = run_mod.main(argv)

    with open(os.path.join(rdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    config = next(r for r in rows if r.get("kind") == "config")
    rounds_rows = [r for r in rows if "kind" not in r]
    telemetry = [r for r in rows if r.get("kind") == "telemetry"][-1]
    losses = [r["train_loss"] for r in rounds_rows]
    check([r["round"] for r in rounds_rows] == list(range(rounds))
          and len(out["history"]) == rounds,
          f"round_idx did not advance 0..{rounds - 1}: {rounds_rows}")
    check(all(math.isfinite(l) for l in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check("test_acc" in rounds_rows[-1], "no eval in the last round")
    check(config["platform"] == ("cpu" if rehearsal else "tpu"),
          f"config row says platform={config['platform']}")
    warm_ts = rounds_rows[0]["ts"]  # round 0 (train + eval) is the warm-up
    late = watch.compiles_since(warm_ts)
    check(late == 0, f"{late} compilation(s) after the warm-up round")
    res = {
        "losses": [round(l, 4) for l in losses],
        "test_acc": rounds_rows[-1]["test_acc"],
        "compile_s": watch.compile_seconds(),
        "programs": len(watch.compiles),
        "cache": watch.cache,
        "compiles_after_warmup": late,
        "round_fn_signatures": telemetry["counters"].get(
            "jax.compiles{fn=round_fn}"),
        "warmup_round_s": round(rounds_rows[0]["time_round"], 2),
        "step_s": [round(r["time_round"], 3) for r in rounds_rows[1:]],
        "peak_bytes_in_use": peak_device_bytes(rehearsal),
        "pallas_kernels": watch.kernel_summary(),
        "attention": "none (conv model)",
        "conv": "XLA conv_general_dilated (models/resnet.py)",
        "run_dir": os.path.relpath(rdir, REPO),
    }
    if not rehearsal:
        res["observed_samples_per_s"] = [
            round(r["count"] / r["time_round"]) for r in rounds_rows[1:]]
    return res


def fused_calls(watch, round_fn, state, args, calls: int):
    """Warm-up call + ``calls`` further ones, each fully synced; returns
    (state, last metrics, per-call loss, per-call seconds, per-call
    compile counts)."""
    import numpy as np

    from fedml_tpu.utils.timing import sync_round

    losses, secs, compiled = [], [], []
    for _ in range(1 + calls):
        mark = time.time()
        t0 = time.perf_counter()
        state, m = round_fn(state, *args)
        check(np.isfinite(sync_round(state, m)), "non-finite metrics")
        secs.append(round(time.perf_counter() - t0, 3))
        compiled.append(watch.compiles_since(mark))
        losses.append(float(np.asarray(m["loss_sum"]).sum()
                            / np.asarray(m["count"]).sum()))
    return state, m, losses, secs, compiled


def leg_resnet56_fused(rehearsal: bool) -> dict:
    """Route 2: the fused driver at bench.py's geometry."""
    watch = start_leg()
    import numpy as np

    from bench import build_north_star

    # bench.py's cohort and its default conv variant; fewer rounds fused
    # per call than its 80, to keep the smoke short
    kw = (dict(clients=2, batch=4, steps=2, rounds_per_call=2)
          if rehearsal else dict(clients=10, batch=64, steps=24,
                                 rounds_per_call=20))
    t0 = time.time()
    round_fn, state, args, samples_per_call = build_north_star(
        conv_variant="s2d1", **kw)
    build_s = round(time.time() - t0, 1)
    state, _, losses, secs, compiled = fused_calls(
        watch, round_fn, state, args, calls=2)
    check(int(np.asarray(state.round_idx)) == 3 * kw["rounds_per_call"],
          f"round_idx={state.round_idx}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(sum(compiled[1:]) == 0,
          f"compilations per call {compiled}: not zero after the warm-up")
    res = {
        "geometry": kw, "losses": [round(l, 4) for l in losses],
        "build_s": build_s, "compile_s": watch.compile_seconds(),
        "cache": watch.cache, "compiles_per_call": compiled,
        "compiles_after_warmup": sum(compiled[1:]),
        "warmup_call_s": secs[0], "step_s": secs[1:],
        "peak_bytes_in_use": peak_device_bytes(rehearsal),
        "pallas_kernels": watch.kernel_summary(),
        "attention": "none (conv model)",
        "conv": "XLA conv_general_dilated, s2d1 retiling "
                "(models/resnet_tpu.py)",
    }
    if not rehearsal:
        res["observed_samples_per_s"] = [
            round(samples_per_call / s) for s in secs[1:]]
    return res


def leg_fedllm_fused(rehearsal: bool) -> dict:
    """The second model on the same path."""
    watch = start_leg()
    import numpy as np

    from bench import build_fedllm

    kw = (dict(clients=2, batch=2, steps=2, seq_len=64, vocab=64,
               embed_dim=32, num_heads=2, num_layers=1, rounds_per_call=2)
          if rehearsal else {})  # bench.py's defaults: width 1280 etc.
    t0 = time.time()
    round_fn, state, args, tokens_per_call, _ = build_fedllm(**kw)
    build_s = round(time.time() - t0, 1)
    state, _, losses, secs, compiled = fused_calls(
        watch, round_fn, state, args, calls=1)
    rpc = kw.get("rounds_per_call", 4)
    check(int(np.asarray(state.round_idx)) == 2 * rpc,
          f"round_idx={state.round_idx}")
    check(sum(compiled[1:]) == 0,
          f"compilations per call {compiled}: not zero after the warm-up")
    embed = state.variables["params"]["wte"]["embedding"].shape
    res = {
        "embedding": list(embed), "losses": [round(l, 4) for l in losses],
        "build_s": build_s, "compile_s": watch.compile_seconds(),
        "cache": watch.cache, "compiles_per_call": compiled,
        "compiles_after_warmup": sum(compiled[1:]),
        "warmup_call_s": secs[0], "step_s": secs[1:],
        "peak_bytes_in_use": peak_device_bytes(rehearsal),
        "pallas_kernels": watch.kernel_summary(),
        "attention": "lax blockwise_attention (the CPU backend)" if rehearsal
                     else "Pallas flash kernels, forward and backward "
                          "(models/transformer.py policy: bf16 on a TPU)",
        "conv": "none",
    }
    names = {name for name, interpreted in watch.kernels if not interpreted}
    check(names == (set() if rehearsal else set(FLASH_KERNELS)),
          f"Pallas kernels traced: {watch.kernels}")
    if not rehearsal:
        res["observed_tokens_per_s"] = [
            round(tokens_per_call / s) for s in secs[1:]]
    return res


# Tolerances of the flash leg, as fractions of max|reference|.  The kernel
# takes bf16 q/k/v, accumulates scores in fp32, rounds the probabilities to
# bf16 for the second matmul and the output to bf16: each rounding is a
# relative 2^-8 = 0.4 %, they do not compound beyond a small multiple, and
# 2 % leaves room for that multiple.  The backward kernel does the same:
# bf16 operands (p and ds rounded to bf16), fp32 scores and accumulators.
FLASH_TOL = 0.02
# the kernels of ops/flash_attention.py, as Watch names them
FLASH_KERNELS = ("_fwd_kernel", "_bwd_kernel")


def leg_flash_attention(rehearsal: bool) -> dict:
    watch = start_leg()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.transformer import _default_attn
    from fedml_tpu.ops.flash_attention import flash_attention, pick_block
    from fedml_tpu.parallel.ring_attention import blockwise_attention

    # (L, heads, head size): the benchmark cells' own shape (gpt2-large,
    # two heads to a 128-lane block), then width 1280 / 10 at the lengths
    # where the lax path no longer fits
    shapes = ([(256, 2, 64), (256, 2, 128)] if rehearsal else
              [(1024, 20, 64), (2048, 10, 128), (8192, 10, 128)])
    res = {"tolerance": FLASH_TOL, "shapes": {}}
    for L, heads, dim in shapes:
        block = pick_block(L, dim)
        if rehearsal:
            # the CPU can only interpret the kernels
            attn = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, block_q=block, block_k=block,
                interpret=True)
        else:
            # the policy the model uses: the kernels for bf16 on a TPU
            attn = lambda q, k, v: _default_attn(q, k, v, True)  # noqa: E731
        ks = jax.random.split(jax.random.PRNGKey(L), 4)
        q, k, v, do = (
            jax.random.normal(kk, (L, heads, dim), jnp.float32)
            .astype(jnp.bfloat16) for kk in ks)
        first = len(watch.kernels)
        out = jax.jit(attn)(q, k, v)
        grads = jax.jit(jax.grad(
            lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                             * do.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        jax.block_until_ready((out, grads))
        traced = watch.kernels[first:]
        # the forward call traces the forward kernel, the gradient call
        # the forward again and the backward kernel
        check([name for name, _ in traced]
              == [FLASH_KERNELS[0], *FLASH_KERNELS],
              f"L={L}: Pallas kernels traced: {traced}")
        check(rehearsal or not any(i for _, i in traced),
              f"L={L}: the kernel ran interpreted: {traced}")
        # reference on the same values in fp32, two heads at a time (its
        # backward keeps every probability block alive)
        with jax.default_matmul_precision("highest"):
            ref = lambda q, k, v: blockwise_attention(  # noqa: E731
                q, k, v, causal=True, block_size=512)
            ref_fwd = jax.jit(ref)
            ref_bwd = jax.jit(jax.grad(
                lambda q, k, v, d: (ref(q, k, v) * d).sum(),
                argnums=(0, 1, 2)))
            o_ref, g_ref = [], []
            for h in range(0, heads, 2):
                a = [t[:, h:h + 2].astype(jnp.float32)
                     for t in (q, k, v, do)]
                o_ref.append(np.asarray(ref_fwd(*a[:3])))
                g_ref.append([np.asarray(g) for g in ref_bwd(*a)])
        errs = {}
        pairs = [("o", out, np.concatenate(o_ref, 1))] + [
            (f"d{n}", g, np.concatenate([x[i] for x in g_ref], 1))
            for i, (n, g) in enumerate(zip("qkv", grads))]
        for name, got, want in pairs:
            rel = max_rel_error(got, want)
            errs[name] = round(rel, 5)
            check(rel <= FLASH_TOL,
                  f"L={L} {name}: max error {rel:.4f} of max|ref| "
                  f"exceeds {FLASH_TOL}")
        res["shapes"][f"L={L},H={heads},D={dim},block={block}"] = errs
    res.update(compile_s=watch.compile_seconds(), cache=watch.cache,
               pallas_kernels=watch.kernel_summary(),
               attention="Pallas flash kernels (ops/flash_attention.py) "
                         + ("interpreted" if rehearsal else "compiled"),
               conv="none",
               peak_bytes_in_use=peak_device_bytes(rehearsal))
    return res


# The conv kernel emits bf16 (one rounding, relative 2^-8 = 0.4 %) from an
# fp32 accumulator; 1 % of max|reference| leaves room for the accumulation
# order.  Its moments are fp32 sums of the values it emitted.
CONV_TOL = 0.01


def leg_conv_mxu(rehearsal: bool) -> dict:
    watch = start_leg()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.conv_mxu import _xla_conv3x3, conv3x3_mxu

    # (image side, Cin, Cout, stride): the 3x3 convs of ResNet-56 — stem,
    # the three stages, the two stride-2 transitions
    shapes = [(32, 3, 16, 1), (32, 16, 16, 1), (32, 16, 32, 2),
              (32, 32, 32, 2), (16, 32, 32, 1), (16, 64, 64, 2),
              (8, 64, 64, 1)]
    n = 64
    if rehearsal:
        shapes, n = [(8, 16, 16, 1), (8, 16, 32, 2)], 8
    res = {"tolerance": CONV_TOL, "shapes": {}}
    for hw, ci, co, stride in shapes:
        kx, kw = jax.random.split(jax.random.PRNGKey(ci * 100 + co + stride))
        x = jax.random.normal(kx, (n, hw, hw, ci)).astype(jnp.bfloat16)
        w = (jax.random.normal(kw, (3, 3, ci, co))
             * (2.0 / (9 * ci)) ** 0.5).astype(jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(
                lambda x, w: _xla_conv3x3(x.astype(jnp.float32),
                                          w.astype(jnp.float32), stride)
            )(x, w))
        first = len(watch.kernels)
        plain = jax.jit(lambda x, w: conv3x3_mxu(x, w, stride=stride))(x, w)
        y, s1, s2 = jax.jit(lambda x, w: conv3x3_mxu(
            x, w, stride=stride, moments=True))(x, w)
        traced = watch.kernels[first:]
        check(len(traced) == 2, f"expected two kernels, traced {traced}")
        check(rehearsal or not any(i for _, i in traced),
              f"the kernel ran interpreted: {traced}")
        tag = f"{ci}->{co}@{hw}s{stride}"
        for got in (plain, y):
            rel = max_rel_error(got, want)
            check(rel <= CONV_TOL, f"{tag}: max error {rel:.4f} of max|ref|")
        yf = np.asarray(y, np.float64)
        check(np.allclose(np.asarray(s1), yf.sum((0, 1, 2)), rtol=1e-4,
                          atol=1e-4 * np.abs(yf).sum((0, 1, 2)).max())
              and np.allclose(np.asarray(s2), (yf * yf).sum((0, 1, 2)),
                              rtol=1e-4), f"{tag}: moments disagree")
        res["shapes"][tag] = round(rel, 5)
    res.update(compile_s=watch.compile_seconds(), cache=watch.cache,
               pallas_kernels=watch.kernel_summary(), attention="none",
               conv="Pallas implicit-GEMM kernel (ops/conv_mxu.py) "
                    + ("interpreted" if rehearsal else "compiled"),
               peak_bytes_in_use=peak_device_bytes(rehearsal))
    return res


def leg_federation_mux(rehearsal: bool) -> dict:
    """One muxer on the chip, hub and server on the host.  This process
    starts them and must itself stay off JAX."""
    import numpy as np

    from fedml_tpu.experiments.distributed_fedavg import launch

    rdir = run_dir("federation_mux")
    out = os.path.join(rdir, "final.npz")
    info = {}
    rc = launch(num_clients=8, rounds=2, seed=1, batch_size=16, out_path=out,
                muxers=1, muxed_clients=8, codec="int8", round_timeout=120.0,
                info=info, env=dict(os.environ), timeout=170.0)
    check("jax" not in sys.modules, "the launcher imported jax")
    check(rc == 0, f"server exit code {rc}")
    z = np.load(out)
    log = [r for r in json.loads(str(z["round_log"])) if "participants" in r]
    check(int(z["rounds"]) == 2 and len(log) == 2, f"rounds: {log}")
    for r in log:
        check(sorted(r["participants"]) == list(range(1, 9)),
              f"incomplete round: {r}")
    leaves = [z[k] for k in z.files if k.startswith("leaf_")]
    check(leaves and all(np.isfinite(l).all() for l in leaves),
          "non-finite leaves")
    platforms = {k[len("platform_"):]: v for k, v in info.items()
                 if k.startswith("platform_")}
    want = "cpu" if rehearsal else "tpu"
    check(platforms.get("mux1") == want and platforms.get("server") == "cpu",
          f"platforms: {platforms}")
    on_chip = [k for k, v in platforms.items() if v != "cpu"]
    check(rehearsal or on_chip == ["mux1"],
          f"exactly one process may hold the chip: {platforms}")
    return {"rc": rc, "platforms": platforms,
            "participants": [r["participants"] for r in log],
            "leaves": len(leaves),
            "attention": "none", "conv": "none (logistic regression)",
            "run_dir": os.path.relpath(rdir, REPO)}


def leg_resnet56_spmd4(rehearsal: bool) -> dict:
    """The same round on four chips, in one process."""
    watch = start_leg()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.fedavg import (ServerState,
                                             resolve_compute_dtype)
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models import resnet
    from fedml_tpu.parallel.spmd import (make_client_mesh, make_spmd_round_fn,
                                         replicate, shard_client_block)

    n_dev = 4
    if rehearsal:
        bundle, (C, S, B, hw) = resnet.resnet20(num_classes=10,
                                                image_size=8), (8, 2, 4, 8)
    else:
        bundle, (C, S, B, hw) = resnet.resnet56(num_classes=10), (8, 24, 64,
                                                                  32)
    mesh = make_client_mesh(n_dev)
    opt = make_client_optimizer("sgd", 0.001, momentum=0.9,
                                weight_decay=0.001)
    local_update = make_local_update(
        bundle, opt, epochs=1, compute_dtype=resolve_compute_dtype("bf16"),
        unroll=4)
    round_fn = make_spmd_round_fn(mesh, local_update)  # donates its state
    rng = np.random.RandomState(0)
    block = shard_client_block(mesh, (  # host arrays, straight to shards
        rng.rand(C, S, B, hw, hw, 3).astype(np.float32),
        rng.randint(0, 10, (C, S, B)).astype(np.int32),
        np.ones((C, S, B), np.float32),
        np.full((C,), S * B, np.float32),
        np.ones((C,), np.float32),
        np.arange(C, dtype=np.int32),
    ))
    for a in block:
        check(len(a.sharding.device_set) == n_dev, f"not spread: {a.sharding}")
    key = jax.random.PRNGKey(0)
    state = replicate(mesh, ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key))
    state, m, losses, secs, compiled = fused_calls(
        watch, round_fn, state, block, calls=2)
    check(int(np.asarray(state.round_idx)) == 3, f"round_idx={state.round_idx}")
    check(float(np.asarray(m["participants"])) == C, f"participants: {m}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(sum(compiled[1:]) == 0,
          f"compilations per round {compiled}: not zero after the warm-up")
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat}
    if not rehearsal:
        vals = list(in_use.values())
        check(all(vals) and max(vals) < 1.5 * min(vals),
              f"cohort not spread evenly over the devices: {in_use}")
    res = {
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "device_order": [d.id for d in mesh.devices.flat],
        "cohort": C, "losses": [round(l, 4) for l in losses],
        "compile_s": watch.compile_seconds(), "cache": watch.cache,
        "compiles_per_round": compiled,
        "compiles_after_warmup": sum(compiled[1:]),
        "warmup_round_s": secs[0], "step_s": secs[1:],
        "bytes_in_use_per_device": in_use,
        "peak_bytes_in_use": peak_device_bytes(rehearsal),
        "pallas_kernels": watch.kernel_summary(),
        "attention": "none (conv model)",
        "conv": "XLA conv_general_dilated (models/resnet.py)",
    }
    if not rehearsal:
        res["observed_samples_per_s"] = [round(C * S * B / s)
                                         for s in secs[1:]]
    return res


LEG_FNS = {
    "probe": leg_probe,
    "resnet56_run": leg_resnet56_run,
    "resnet56_fused": leg_resnet56_fused,
    "fedllm_fused": leg_fedllm_fused,
    "flash_attention": leg_flash_attention,
    "conv_mxu": leg_conv_mxu,
    "federation_mux": leg_federation_mux,
    "resnet56_spmd4": leg_resnet56_spmd4,
}


def child_main(leg: str, rehearsal: bool) -> None:
    """Run one leg in this process.  Nothing here catches its exceptions:
    a failed check is a traceback and a non-zero exit code."""
    if leg not in ("probe", "federation_mux"):
        import jax

        platform = jax.default_backend()
        want = "cpu" if rehearsal else "tpu"
        if platform != want:
            raise SystemExit(
                f"chip_smoke leg {leg}: found platform={platform}, "
                f"needs {want}")
    result = LEG_FNS[leg](rehearsal)
    print(RESULT_MARK + json.dumps(result, default=str), flush=True)


# ---------------------------------------------------------------------------
# parent side: never imports jax
# ---------------------------------------------------------------------------


class Parent:
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.child = None
        self.deadline = time.monotonic() + TOTAL_BUDGET_S
        signal.signal(signal.SIGTERM, self._on_signal)
        signal.signal(signal.SIGINT, self._on_signal)

    def say(self, text: str) -> None:
        prefix = REHEARSAL_TAG + " | " if self.rehearsal else ""
        for line in text.splitlines() or [""]:
            print(prefix + line, flush=True)

    def _kill_child(self) -> None:
        """Stop the running leg and everything it started (the leg leads
        its own session, so the federation's processes go with it)."""
        child = self.child
        if child is not None and child.poll() is None:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass

    def _on_signal(self, signum, _frame):
        self._kill_child()
        raise SystemExit(128 + signum)

    def run_leg(self, leg: str, budget_s: float) -> dict:
        """Run one leg to its end in a child process and return its
        result; a leg that fails ends the smoke."""
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg]
        if self.rehearsal:
            cmd.append("--cpu-rehearsal")
        budget_s = min(budget_s, self.deadline - time.monotonic())
        if budget_s <= 0:
            raise SystemExit(f"chip_smoke: out of time before leg {leg}")
        t0 = time.monotonic()
        self.child = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        timer = threading.Timer(budget_s, self._kill_child)
        timer.start()
        result = None
        try:
            for line in self.child.stdout:
                line = line.rstrip("\n")
                if line.startswith(RESULT_MARK):
                    result = json.loads(line[len(RESULT_MARK):])
                else:
                    self.say(f"  [{leg}] {line}")
            rc = self.child.wait()
        finally:
            timer.cancel()
            self._kill_child()  # stragglers of the leg's session
            self.child = None
        wall = round(time.monotonic() - t0, 1)
        if rc != 0 or result is None:
            self.say(f"leg {leg}: FAILED (exit code {rc}, {wall} s"
                     + (f", killed at its {budget_s:.0f} s limit"
                        if rc == -signal.SIGKILL else "") + ")")
            raise SystemExit(1)
        result["wall_s"] = wall
        return result


def parent_main(rehearsal: bool) -> None:
    for needed in ("fedml_tpu", "bench.py"):
        if not os.path.exists(os.path.join(REPO, needed)):
            raise SystemExit(
                f"chip_smoke: {needed} is not beside this script in {REPO}; "
                "it checks the repository it is part of")
    p = Parent(rehearsal)
    device = p.run_leg("probe", 120)
    p.say("platform={platform} device_kind={device_kind!r} "
          "device_count={device_count} jax={jax} jaxlib={jaxlib} "
          "libtpu={libtpu}".format(**device))
    p.say(f"compile cache: {device['compile_cache']}")
    p.say(f"cohort packer: {device['packer']}")
    if device["gxx"] == "missing":
        p.say("g++ is missing on this machine: cohorts are packed with numpy")
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want:
        raise SystemExit(
            f"chip_smoke: found platform={device['platform']} "
            f"({device['device_kind']}), needs {want}"
            + ("" if rehearsal else
               "; there is no accelerator here and no result"))
    results = {}
    for leg, budget_s in LEGS:
        if leg == "resnet56_spmd4" and device["device_count"] < 4:
            p.say(f"leg {leg}: not run: {device['device_count']} device")
            continue
        res = results[leg] = p.run_leg(leg, budget_s)
        p.say(f"leg {leg}: ok  " + json.dumps(res))
    jax_legs = [r for r in results.values() if "compile_s" in r]
    p.say("compile seconds (all legs): %.1f  cache hits/misses: %d/%d" % (
        sum(r["compile_s"] for r in jax_legs),
        sum(r["cache"]["hits"] for r in jax_legs),
        sum(r["cache"]["misses"] for r in jax_legs)))
    p.say("compilations after warm-up: %d" % sum(
        r.get("compiles_after_warmup", 0) for r in results.values()))
    peaks = [r["peak_bytes_in_use"] for r in results.values()
             if r.get("peak_bytes_in_use")]
    p.say("peak device memory: "
          + (f"{max(peaks)} bytes" if peaks else "not reported on the cpu"))
    summary = {"ok": True, "device": {"platform": device["platform"],
                                      "kind": device["device_kind"],
                                      "count": device["device_count"]}}
    if rehearsal:
        summary["rehearsal"] = True  # not a result: nothing ran on a chip
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = "chip_smoke_rehearsal.json" if rehearsal else "chip_smoke.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump({**summary, "probe": device, "legs": results}, f, indent=1)
    p.say(json.dumps(summary))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the CPU; every line says so and no "
                    "device metric is written")
    ap.add_argument("--leg", choices=sorted(LEG_FNS),
                    help="(internal) run one leg in this process")
    args = ap.parse_args()
    if args.leg:
        child_main(args.leg, args.cpu_rehearsal)
    else:
        parent_main(args.cpu_rehearsal)


if __name__ == "__main__":
    main()
