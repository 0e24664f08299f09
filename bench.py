"""Benchmark: FedAvg local-training throughput + aggregation, north-star
workload (ResNet-56 / CIFAR-10-shaped data, batch 64 — BASELINE.md).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": "tpu", "device_kind": ..., "device_count": N}

Runs on a TPU only: with no chip it exits with an error that names the
platform it found, and prints no number.

The reference publishes no throughput numbers (BASELINE.md), so
``vs_baseline`` is computed against an estimated reference-hardware
figure: PyTorch ResNet-56/CIFAR-10 training on the RTX-2080-Ti-class
GPUs the reference's cluster used sustains roughly 1500 samples/s per
GPU (per-client serial training, as in the reference's one-process-per-
client design). vs_baseline = our samples/s / 1500.

Execution mode: the compiled multi-round driver
(``make_multi_round_fn``) — ``--rounds-per-call`` federated rounds fused
into one program, so the device never sits idle waiting for the host
between rounds.  ``--rounds-per-call 1`` benchmarks the per-round
dispatch path instead.

Timing methodology (shared: fedml_tpu/utils/timing.py): warm up until
two consecutive fully-synced calls agree, then report the median
per-call wall-clock with the scalar readback inside the timed window.
No rate is quoted here: the only chip numbers the repo holds
(PROFILE.md rounds 1-6) were taken before this round of work, under
another runtime and JAX; the ledger carries the current ones.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

REFERENCE_GPU_SAMPLES_PER_SEC = 1500.0

# workload-aware rounds-per-call defaults (single source of truth for
# the CLI and the build_* signatures)
NORTH_STAR_RPC = 80
FEDLLM_RPC = 4


def build_north_star(
    clients: int = 10,
    batch: int = 64,
    steps: int = 24,
    epochs: int = 1,
    dtype: str = "bf16",
    unroll: int = 4,
    rounds_per_call: int = NORTH_STAR_RPC,
    client_unroll: int = 1,
    conv_variant: str = "baseline",
):
    """The canonical bench workload, shared with tools/scaling_model.py
    so the scaling model's measured t_compute is BY CONSTRUCTION the
    bench protocol's configuration.  Returns (round_fn, state, args,
    samples_per_call)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import (
        ServerState,
        make_multi_round_fn,
        resolve_compute_dtype,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    if conv_variant == "baseline":
        from fedml_tpu.models.resnet import resnet56

        bundle = resnet56(num_classes=10)
    else:
        # TPU-retiled EXECUTION variants of the SAME model (identical
        # params + function, pinned by tests/test_resnet_tpu.py +
        # tests/test_conv_mxu.py): s2d1/s2d2/s2d3 = space-to-depth
        # through stages 1..k; pad32 = stage-1 lane padding; pallas =
        # implicit-GEMM Pallas 3×3 conv kernel with moment-fused BN
        from fedml_tpu.models.resnet_tpu import resnet56_tpu

        kw = {"s2d1": {"s2d_stages": 1}, "s2d2": {"s2d_stages": 2},
              "s2d3": {"s2d_stages": 3},
              "pad32": {"pad_stage1_to": 32},
              "pallas": {"conv_variant": "pallas"}}[conv_variant]
        bundle = resnet56_tpu(num_classes=10, **kw)
    opt = make_client_optimizer("sgd", 0.001, momentum=0.9, weight_decay=0.001)
    local_update = make_local_update(
        bundle, opt, epochs=epochs,
        compute_dtype=resolve_compute_dtype(dtype), unroll=unroll,
    )
    round_fn = jax.jit(
        make_multi_round_fn(local_update, rounds_per_call,
                            client_unroll=client_unroll)
    )
    rng = np.random.RandomState(0)
    C, S, B = clients, steps, batch
    args = (
        jnp.asarray(rng.rand(C, S, B, 32, 32, 3).astype(np.float32)),
        jnp.asarray(rng.randint(0, 10, (C, S, B)).astype(np.int32)),
        jnp.ones((C, S, B), jnp.float32),
        jnp.full((C,), S * B, jnp.float32),
        jnp.ones((C,), jnp.float32),
        jnp.arange(C, dtype=jnp.int32),
    )
    key = jax.random.PRNGKey(0)
    state = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    return round_fn, state, args, C * S * B * epochs * rounds_per_call


# bf16 peak FLOP/s of one chip, keyed by the ``device_kind`` string JAX
# reports for it.  Source: Google Cloud documentation, "TPU v5e" system
# architecture — 197 TFLOP/s bf16 per chip; the chip reports itself as
# "TPU v5 lite" (read off the device, chip_smoke.py prints it).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    """The table's entry for ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device_kind={device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); add it to "
            "PEAK_BF16_FLOPS with its source"
        )
    return PEAK_BF16_FLOPS[device_kind]


def build_fedllm(
    clients: int = 4,
    batch: int = 8,
    steps: int = 4,
    seq_len: int = 1024,
    vocab: int = 8192,
    embed_dim: int = 1280,
    num_heads: int = 10,
    num_layers: int = 12,
    epochs: int = 1,
    dtype: str = "bf16",
    unroll: int = 1,
    rounds_per_call: int = FEDLLM_RPC,
    remat: bool = False,
):
    """MXU-friendly federated-LLM workload (the ``fedllm`` experiment
    family): next-token training of a GPT-2-shaped decoder (default
    width 1280 = GPT-2-Large's, 12 layers) over a packed client axis.  Exists to measure the framework's MFU on a
    model whose matmuls CAN tile the MXU (VERDICT r3 weak #3: ResNet-56's
    16/32/64-wide convs cap the north-star workload at a 25-30%
    structural ceiling; this workload demonstrates where the ceiling is
    the model, not the framework).

    Returns (round_fn, state, args, tokens_per_call, flops_per_token).
    """
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import (
        ServerState,
        make_multi_round_fn,
        resolve_compute_dtype,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.transformer import transformer_lm

    bundle = transformer_lm(
        vocab_size=vocab, embed_dim=embed_dim, num_heads=num_heads,
        num_layers=num_layers, seq_len=seq_len, remat=remat,
    )
    opt = make_client_optimizer("sgd", 3e-4)
    local_update = make_local_update(
        bundle, opt, epochs=epochs,
        compute_dtype=resolve_compute_dtype(dtype), unroll=unroll,
    )
    round_fn = jax.jit(
        make_multi_round_fn(local_update, rounds_per_call)
    )
    rng = np.random.RandomState(0)
    C, S, B, L = clients, steps, batch, seq_len
    toks = rng.randint(0, vocab, (C, S, B, L)).astype(np.int32)
    args = (
        jnp.asarray(toks),
        jnp.asarray(np.roll(toks, -1, axis=-1)),
        jnp.ones((C, S, B), jnp.float32),
        jnp.full((C,), S * B * L, jnp.float32),
        jnp.ones((C,), jnp.float32),
        jnp.arange(C, dtype=jnp.int32),
    )
    key = jax.random.PRNGKey(0)
    state = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    # exact matmul FLOP accounting, fwd+bwd = 3x fwd (standard 2P rule
    # per matmul; embedding LOOKUP is free, the weight-tied head is a
    # [*, d] @ [d, V] matmul):
    #   per layer / token: qkv+proj 2*4d^2, mlp 2*8d^2, attention
    #   scores+values 2*2*L*d
    per_token_fwd = (
        num_layers * (2 * 12 * embed_dim**2 + 4 * seq_len * embed_dim)
        + 2 * embed_dim * vocab
    )
    flops_per_token = 3 * per_token_fwd
    tokens_per_call = C * S * B * L * epochs * rounds_per_call
    return round_fn, state, args, tokens_per_call, flops_per_token


def main():
    p = argparse.ArgumentParser()
    # 10 clients all participating = the reference's cross-silo ResNet-56
    # benchmark cohort (BASELINE.md: "10 clients all participating,
    # E=20, batch 64")
    p.add_argument("--clients", type=int, default=None,
                   help="default: 10 (north_star) / 4 (fedllm)")
    p.add_argument("--batch", type=int, default=None,
                   help="default: 64 (north_star) / 8 (fedllm — batch 64 "
                   "of the 1280-wide LM would OOM v5e HBM)")
    p.add_argument("--steps", type=int, default=None,
                   help="default: 24 (north_star) / 4 (fedllm)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--rounds", type=int, default=4,
                   help="measured multi-round calls (median over these)")
    p.add_argument(
        "--rounds-per-call", type=int, default=None,
        help="federated rounds fused per compiled call "
        "(make_multi_round_fn); 1 = per-round dispatch path. Default "
        "is workload-aware: north_star 80, fedllm 4",
    )
    p.add_argument(
        "--unroll", type=int, default=4,
        help="step-scan unroll inside the local update (TPU while-loop "
        "bookkeeping is ~0.3ms/iteration; 4 measured best on v5e)",
    )
    p.add_argument(
        "--client-unroll", type=int, default=1,
        help="unroll of the sequential client loop (1 = lax.map); trades "
        "compiled-code size for fewer while-loop iterations",
    )
    p.add_argument(
        "--dtype",
        default="bf16",
        help="compute dtype for the local-training forward/backward. "
        "bf16 = mixed precision (fp32 masters/optimizer/aggregation): "
        "~1.5-2x fp32 on the MXU; convergence parity with fp32 is "
        "unit-tested (tests/test_fedavg.py::test_fedavg_mixed_precision_bf16).",
    )
    p.add_argument(
        "--workload", choices=["north_star", "fedllm"],
        default="north_star",
        help="north_star = the driver's headline ResNet-56 cross-silo "
        "throughput; fedllm = GPT-2-small-shaped federated next-token "
        "training, reported as MFU (the second perf datapoint — "
        "demonstrates the framework on an MXU-friendly model)",
    )
    p.add_argument(
        "--conv-variant",
        choices=["baseline", "s2d1", "s2d2", "s2d3", "pad32", "pallas"],
        default="s2d1",
        help="north_star conv execution variant (models/resnet_tpu.py): "
        "same model/params/function (parity-tested), retiled for MXU "
        "lanes — s2dK folds 2x2 spatial blocks into channels through "
        "stage K; pad32 zero-pads stage-1's 16-wide convs to 32 lanes; "
        "pallas runs every 3x3 conv as an implicit-GEMM Pallas kernel "
        "(ops/conv_mxu: [M, 9*Cin] patch matrix, one MXU matmul, "
        "moment-fused train BN). r5 sweep on v5e (samples/s): baseline "
        "28,828; s2d1 29,897 (default — +3.7%); s2d2 26,909; s2d3 "
        "22,370; pad32 24,673 — see PROFILE.md for the tile math; the "
        "pallas variant first compiled in PR 21 and is ROADMAP A2's to "
        "measure",
    )
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--embed-dim", type=int, default=1280,
                   help="1280/h10 measured best on v5e (width sweep at "
                   "rounds-per-call 1: 768=24.2%, 1024=37.7%, "
                   "1280=40.8%; the rpc=4 default lifts 1280 to 47.5% "
                   "by amortizing dispatch); 1536 OOMs HBM at batch "
                   "8x1024 without remat")
    p.add_argument("--num-layers", type=int, default=12)
    p.add_argument("--num-heads", type=int, default=10)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument(
        "--remat", action="store_true",
        help="checkpoint each transformer Block (recompute activations "
        "in the backward): ~1/3 more FLOPs for O(layers) less live HBM "
        "— required for width >=1536 at batch 8x1024 on one v5e",
    )
    args = p.parse_args()
    # workload-aware defaults: the fedllm model is ~50x the FLOPs and
    # memory per sample of the ResNet workload, so sharing the
    # north-star cohort defaults would OOM the chip
    wd = ({"clients": 10, "batch": 64, "steps": 24,
           "rounds_per_call": NORTH_STAR_RPC}
          if args.workload == "north_star"
          else {"clients": 4, "batch": 8, "steps": 4,
                "rounds_per_call": FEDLLM_RPC})
    for k, v in wd.items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    from fedml_tpu.utils.compile_cache import configure_compile_cache
    from fedml_tpu.utils.device import device_report, require_tpu

    configure_compile_cache()
    require_tpu()  # before anything is built: no chip, no number
    device = device_report()

    # shared methodology (fedml_tpu/utils/timing.py): warm until two
    # consecutive fully-synced calls agree, then median of per-call
    # times with the scalar readback INSIDE the timed window
    from fedml_tpu.utils.timing import measure_rounds

    if args.workload == "fedllm":
        peak = peak_bf16_flops(device["device_kind"])  # unknown chip: stop
        round_fn, state, call_args, tokens_per_call, fpt = build_fedllm(
            clients=args.clients, batch=args.batch, steps=args.steps,
            seq_len=args.seq_len, vocab=args.vocab,
            embed_dim=args.embed_dim, num_heads=args.num_heads,
            num_layers=args.num_layers, epochs=args.epochs,
            dtype=args.dtype, unroll=args.unroll,
            rounds_per_call=args.rounds_per_call, remat=args.remat,
        )
        med, state = measure_rounds(round_fn, state, call_args, args.rounds)
        tflops = tokens_per_call * fpt / med
        mfu = tflops / peak
        print(
            json.dumps(
                {
                    "metric": "fedllm_transformer_local_train_mfu",
                    "value": round(100 * mfu, 1),
                    "unit": "percent_of_bf16_peak",
                    # vs the north-star workload's structural ceiling
                    # story: >1.0 means this clears ResNet-56's measured
                    # 11% MFU, substantiating "the model was the
                    # ceiling, not the framework"
                    "vs_baseline": round(mfu / 0.11, 2),
                    "detail": {
                        "tokens_per_s": round(tokens_per_call / med),
                        "model_tflops_per_s": round(tflops / 1e12, 1),
                        "flops_per_token": fpt,
                        "peak_bf16_flops": peak,
                        "config": {
                            "embed_dim": args.embed_dim,
                            "num_layers": args.num_layers,
                            "num_heads": args.num_heads,
                            "seq_len": args.seq_len,
                            "vocab": args.vocab,
                            "clients": args.clients,
                            "batch": args.batch,
                            "steps": args.steps,
                            "rounds_per_call": args.rounds_per_call,
                            "epochs": args.epochs,
                            "unroll": args.unroll,
                            "dtype": args.dtype,
                        },
                    },
                    **device,
                }
            )
        )
        return

    round_fn, state, call_args, samples_per_call = build_north_star(
        clients=args.clients, batch=args.batch, steps=args.steps,
        epochs=args.epochs, dtype=args.dtype, unroll=args.unroll,
        rounds_per_call=args.rounds_per_call,
        client_unroll=args.client_unroll,
        conv_variant=args.conv_variant,
    )
    med, state = measure_rounds(round_fn, state, call_args, args.rounds)
    sps = samples_per_call / med
    print(
        json.dumps(
            {
                "metric": "fedavg_resnet56_cifar10_local_train_throughput",
                "value": round(sps, 1),
                "unit": "samples/sec",
                "vs_baseline": round(sps / REFERENCE_GPU_SAMPLES_PER_SEC, 3),
                **device,
            }
        )
    )


if __name__ == "__main__":
    main()
