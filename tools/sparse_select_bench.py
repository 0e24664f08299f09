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
  select_ms      ``select_topk`` whole: scores, threshold, ties, mask, tiles
  same_choice    whether ``select_topk`` and ``select_by_sort`` kept the same
                 pairs, and ``kept`` how many
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--skip-sort", action="store_true")
    ap.add_argument("--operands", default="float32",
                    help="dtype of qI and kI: float32 (six MXU passes a "
                         "product) or bfloat16 (one), as the layer's x")
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
    select = jax.jit(jax.vmap(lambda *i: ss.select_topk(*i, topk)))
    out["select_ms"] = timed_ms(select, qI, kI, w, calls=args.calls)
    keep, tiles = select(qI, kI, w)
    out["kept"] = int(keep.astype(jnp.int32).sum())
    out["tiles_live"] = int(tiles.sum())
    if not args.skip_sort:
        top_k = jax.jit(jax.vmap(lambda s: lax.top_k(s, topk)[0][:, -1]))
        out["top_k_ms"] = timed_ms(top_k, masked, calls=args.calls)
        by_sort = jax.jit(jax.vmap(lambda s: ss.select_by_sort(s, topk)))
        out["by_sort_ms"] = timed_ms(by_sort, scores, calls=args.calls)
        out["same_choice"] = bool((by_sort(scores) == keep).all())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
