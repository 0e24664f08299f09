"""The expert layer's grouped products alone, on the chip, at each decoder
configuration's own short buffer (C rows), widths (h, f) and group sizes:
groups laid one after another from row 0 (the kernel computes a row tile
that two groups share once for each) against every group starting on a row
tile and handed its size rounded up to it, at a row tile of 512 and of 256,
forward and backward (``megablox``'s own VJP: the transposed ``gmm`` and
``tgmm``).

    python3 benchmark/tools/expert_tiles_bench.py [--calls 5]   # on a TPU

Group sizes: one draw of a level router (each held expert's rows binomial
over ``tokens`` with ``top_k / routed``) from ``--seed``, the same for every
layout and tile.  One JSON line a configuration, product and variant, then
one with every line:

  rows_computed   rows the grid computes (``rows_computed``: the row tiles
                  ``make_group_metadata`` visits x the tile)
  fwd_ms          device ms of the forward ``gmm``
  bwd_ms          device ms of the backward: the transposed ``gmm`` and ``tgmm``
  tile_pct        FLOPs of the rows computed, forward and backward, over the
                  device time at the chip's bf16 peak: the kernel's speed a
                  computed tile
  useful_pct      the same for the rows routed only: the share of the peak
                  that earns something
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONFIGS = {  # cell: configuration file
    "mellum2_silo_code8k": "mellum2-12b-a2.5b.json",
    "keyevl2_silo_text8k": "keye-vl-2.0-30b-a3b.json",
    "trinitymini_silo_chat8k": "trinity-mini.json",
    "kimilin_silo_doc8k": "kimi-linear-48b-a3b.json",
}


def kernel_ms(fn, *args, calls: int) -> dict:
    """Mean device milliseconds a call of ``fn`` spends in ``gmm`` and in
    ``tgmm`` custom calls on the first TPU's op line."""
    import jax

    from benchmark.trace_reduce import load_profile

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        path, = glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", "*.xplane.pb"))
        planes = load_profile(path)
    found = {"gmm": 0.0, "tgmm": 0.0}
    for plane in planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if e.stats.get("hlo_category") != "custom-call":
                    continue
                names = (e.name, str(e.stats.get("deduplicated_name", "")),
                         str(e.stats.get("tf_op", "")))
                kind = ("tgmm" if any("tgmm" in n for n in names) else
                        "gmm" if any("gmm" in n for n in names) else None)
                if kind:
                    found[kind] += (e.end - e.start) / 1e6
    return {kind: ms / calls for kind, ms in found.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmark import cells, peaks
    from fedml_tpu.models.decoder import (
        DecoderConfig, buffer_capacities, gmm_tiling, rows_computed,
    )

    peak = peaks.peaks(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    rows_out = []
    for cell, name in CONFIGS.items():
        c = DecoderConfig.from_dict(cells.read_json("configs", name))
        T, k, routed = c.max_len, c.top_k, c.num_experts_routed
        held, h, f = len(c.experts_held), c.hidden_size, c.moe_intermediate_size
        capacity = buffer_capacities(T, k, held, routed)[0]
        rng = np.random.default_rng(args.seed)
        sizes = rng.binomial(T, k / routed, size=held).astype(np.int32)
        for product, (d_in, d_out) in (("gate_up", (h, f)), ("down", (f, h))):
            ks = jax.random.split(jax.random.PRNGKey(d_in + d_out), 3)
            w = jax.random.normal(ks[0], (held, d_in, d_out),
                                  jnp.bfloat16) * 0.02
            _, tk, tn = gmm_tiling(capacity, d_in, d_out)
            for tm in (512, 256):
                for layout in ("unaligned", "aligned"):
                    given = (sizes if layout == "unaligned"
                             else -(-sizes // tm) * tm)
                    total = int(given.sum())
                    if total > capacity:
                        rows_out.append({"cell": cell, "product": product,
                                         "tm": tm, "layout": layout,
                                         "skipped": "past the buffer"})
                        continue
                    live = (jnp.arange(capacity) < total)[:, None]
                    x = jnp.where(live, jax.random.normal(
                        ks[1], (capacity, d_in), jnp.bfloat16), 0)
                    dy = jnp.where(live, jax.random.normal(
                        ks[2], (capacity, d_out), jnp.bfloat16), 0)
                    gs = jnp.asarray(given)
                    tiling = (tm, tk, tn)

                    def dot(x, w, gs, tiling=tiling):
                        return gmm(x, w, gs, jnp.bfloat16, tiling)

                    def both(x, w, gs, dy, dot=dot):
                        y, pull = jax.vjp(lambda x, w: dot(x, w, gs), x, w)
                        return y, pull(dy)

                    fwd = kernel_ms(jax.jit(dot), x, w, gs, calls=args.calls)
                    fb = kernel_ms(jax.jit(both), x, w, gs, dy,
                                   calls=args.calls)
                    computed = int(rows_computed(gs, tm))
                    seconds = (fb["gmm"] + fb["tgmm"]) / 1e3
                    flops = 3 * 2 * d_in * d_out
                    row = {"cell": cell, "product": product,
                           "C": capacity, "d_in": d_in, "d_out": d_out,
                           "tm": tm, "layout": layout,
                           "rows_routed": int(sizes.sum()),
                           "rows_given": total, "rows_computed": computed,
                           "fwd_ms": round(fwd["gmm"], 4),
                           "bwd_ms": round(fb["gmm"] + fb["tgmm"]
                                           - fwd["gmm"], 4),
                           "fwd_bwd_ms": round(seconds * 1e3, 4),
                           "tile_pct": round(100 * flops * computed
                                             / seconds / peak, 2),
                           "useful_pct": round(100 * flops * int(sizes.sum())
                                               / seconds / peak, 2)}
                    print(json.dumps(row), flush=True)
                    rows_out.append(row)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "seed": args.seed, "rows": rows_out}), flush=True)


if __name__ == "__main__":
    main()
