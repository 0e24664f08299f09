"""The choice of a sparse-attention layer alone, on the chip: the parts of
``fedml_tpu/ops/sparse_select.py`` at the shape of the cell
``keyevl2_silo_text8k`` (L = 8192, 16 index heads of 64, ``topk`` 2048), under
the layer's ``vmap`` over a batch of one.

    chiprun -- python3 tools/sparse_select_bench.py [--calls 5] [--length 8192]

One JSON line, milliseconds a call (the median of ``--calls`` synced calls
after a warm-up):

  scores_ms      ``index_scores``: the [L, L] float32 scores written to HBM
  top_k_ms       ``lax.top_k`` of those scores, k = ``topk``: the definition's
                 sort, and what finding the threshold by it would cost
  bisect_ms      the k-th largest of every row by 32 compare-and-count passes
                 over the same [L, L] array
  by_sort_ms     ``select_by_sort`` whole: top_k and the scatter into a mask
  select_ms      ``select_in_lax`` whole, the lax form called by name:
                 scores, threshold, ties, mask, tiles, every row block against
                 all the keys
  kernel_ms      ``select_in_kernel`` whole, the Pallas kernel called by name:
                 a row block against its causal key tiles only; ``scored_ms``
                 of it with ``topk`` = L, where no block needs a threshold:
                 the scores, the mask and the table without the 32 counts
  *_device_ms    the same three calls' device time: how long some op of one
                 call ran, from a profiler trace, which leaves out what the
                 host and the 64 MB result cost a lone call
  same_choice    whether the lax form and ``select_by_sort`` kept the same
                 pairs, and ``kept`` how many; ``kernel_same_choice`` the same
                 for the kernel, ``kernel_pairs_off`` the pairs it decided
                 otherwise than the lax form (a head sum's last bits) and
                 ``kernel_same_tiles`` whether its table is ``live_tiles`` of
                 its mask
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HEADS, DIM, TOPK = 16, 64, 2048


def timed_ms(fn, *args, calls: int):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def device_ms(fn, *args):
    """Device milliseconds of one call of the jitted ``fn``: the time some op
    of it ran, from a trace (a ``while`` spans its body's ops, so the ops'
    intervals are united, not summed)."""
    import jax

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(fn(*args))
        path, = glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(path)
    spans = sorted((event.start_ns, event.start_ns + event.duration_ns)
                   for plane in profile.planes
                   if plane.name.startswith("/device:TPU:0")
                   for line in plane.lines if line.name == "XLA Ops"
                   for event in line.events)
    busy, done = 0.0, 0.0
    for start, end in spans:
        busy += max(0.0, end - max(start, done))
        done = max(done, end)
    return busy / 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--skip-sort", action="store_true")
    ap.add_argument("--operands", default="bfloat16",
                    help="dtype of qI and kI: bfloat16 (one MXU pass a "
                         "product, what the cell runs and the kernel takes) "
                         "or float32 (six)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from fedml_tpu.ops import sparse_select as ss

    L = args.length
    topk = min(TOPK, L // 4)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    qI = jax.random.normal(keys[0], (1, L, HEADS, DIM))
    kI = jax.random.normal(keys[1], (1, L, DIM))
    w = jax.random.normal(keys[2], (1, L, HEADS)) / HEADS ** 0.5
    qI, kI = qI.astype(args.operands), kI.astype(args.operands)

    scores_fn = jax.jit(jax.vmap(ss.index_scores))
    scores = scores_fn(qI, kI, w)
    causal = jnp.tril(jnp.ones((L, L), bool))
    masked = jnp.where(causal, scores, -jnp.inf)

    out = {"device": jax.devices()[0].device_kind, "length": L, "topk": topk,
           "operands": args.operands,
           "scores_ms": timed_ms(scores_fn, qI, kI, w, calls=args.calls)}
    bisect = jax.jit(jax.vmap(lambda s: ss._kth_largest(ss._ordered(s), topk)))
    out["bisect_ms"] = timed_ms(bisect, masked, calls=args.calls)
    select = jax.jit(jax.vmap(lambda *i: ss.select_in_lax(*i, topk)))
    out["select_ms"] = timed_ms(select, qI, kI, w, calls=args.calls)
    if jax.default_backend() == "tpu":
        out["select_device_ms"] = device_ms(select, qI, kI, w)
    keep, tiles = select(qI, kI, w)
    out["kept"] = int(keep.astype(jnp.int32).sum())
    out["tiles_live"] = int(tiles.sum())
    in_kernel = ss.kernel_tiles(qI[0], kI[0])  # a TPU and a shape that tiles
    if in_kernel:
        kernel = jax.jit(jax.vmap(lambda *i: ss.select_in_kernel(*i, topk)))
        out["kernel_ms"] = timed_ms(kernel, qI, kI, w, calls=args.calls)
        scored = jax.jit(jax.vmap(lambda *i: ss.select_in_kernel(*i, L)))
        out["scored_ms"] = timed_ms(scored, qI, kI, w, calls=args.calls)
        out["kernel_device_ms"] = device_ms(kernel, qI, kI, w)
        out["scored_device_ms"] = device_ms(scored, qI, kI, w)
        kernel_keep, kernel_tiles = kernel(qI, kI, w)
        out["kernel_pairs_off"] = int((kernel_keep != keep).sum())
        out["kernel_same_tiles"] = bool((jax.vmap(
            lambda k: ss.live_tiles(k, ss.ROWS))(kernel_keep)
            == kernel_tiles).all())
    if not args.skip_sort:
        top_k = jax.jit(jax.vmap(lambda s: lax.top_k(s, topk)[0][:, -1]))
        out["top_k_ms"] = timed_ms(top_k, masked, calls=args.calls)
        by_sort = jax.jit(jax.vmap(lambda s: ss.select_by_sort(s, topk)))
        out["by_sort_ms"] = timed_ms(by_sort, scores, calls=args.calls)
        out["same_choice"] = bool((by_sort(scores) == keep).all())
        if in_kernel:
            out["kernel_same_choice"] = bool(
                (by_sort(scores) == kernel_keep).all())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
