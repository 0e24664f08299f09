"""The flash kernels alone, on the chip: ``flash_fwd`` and ``flash_bwd`` of
``fedml_tpu/ops/flash_attention.py`` at each benchmark cell's attention shape,
forward and the three gradients in one jitted call under the model's ``vmap``.

    chiprun -- python3 tools/flash_bench.py [--root DIR] [--calls 5]

``--root`` is the checkout whose ``fedml_tpu`` is imported (default: this
one), so that a parent unpacked beside it runs the same bench.  One JSON line
a shape, then one with every shape:

  fwd_ms, bwd_ms   device milliseconds of the ``flash_fwd`` / ``flash_bwd``
                   custom call of one call (the median over ``--calls`` calls
                   in one profiler trace): a layer-step at the cell's shape
  o_sum, o_abs_sum the forward's ``o`` summed, and its magnitudes: the same
                   inputs on two checkouts give the same numbers where their
                   kernels agree
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

# (name, batch, L, q heads, k/v heads, q/k head size, v head size, window,
# choice): the cells' layers
SHAPES = [
    ("keyevl2_choice", 1, 8192, 32, 4, 128, 128, None, True),
    ("causal_32_on_4", 1, 8192, 32, 4, 128, 128, None, False),
    ("trinitymini_window2048", 1, 8192, 32, 4, 128, 128, 2048, False),
    ("mellum2_window1024", 1, 8192, 4, 1, 128, 128, 1024, False),
    ("mellum2_full", 1, 8192, 4, 1, 128, 128, None, False),
    ("gpt2l_causal_d64", 8, 1024, 20, 20, 64, 64, None, False),
    ("kimilin_latent_192_128", 1, 8192, 4, 4, 192, 128, None, False),
]
TOPK = 2048  # keys a query the cell's indexer picks (16 heads of 64)


def kernel_ms(fn, *args, calls: int) -> dict:
    """Median device milliseconds of each flash kernel of ``fn``: the custom
    calls on the first TPU's op line whose name or ``tf_op`` names it (read
    as ``benchmark/trace_reduce.py`` reads a cell's trace)."""
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
    found = {"fwd": [], "bwd": []}
    for plane in planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if e.stats.get("hlo_category") != "custom-call":
                    continue  # not a copy the compiler names after the call
                names = (e.name, str(e.stats.get("deduplicated_name", "")),
                         str(e.stats.get("tf_op", "")))
                for kind in found:
                    if any(f"flash_{kind}" in n for n in names):
                        found[kind].append((e.end - e.start) / 1e6)
    return {f"{kind}_ms": statistics.median(ms) if ms else None
            for kind, ms in found.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()
    # the bench's own repository for ``benchmark``, the root's ``fedml_tpu``
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import sparse_select as ss
    from fedml_tpu.ops.flash_attention import flash_attention, pick_block

    rows = []
    for name, B, L, H, G, D, Dv, window, chosen in SHAPES:
        block = pick_block(L, D)
        ks = jax.random.split(jax.random.PRNGKey(L + H + D), 8)
        q, k = (jax.random.normal(ks[i], (B, L, h, D), jnp.bfloat16)
                for i, h in ((0, H), (1, G)))
        v = jax.random.normal(ks[2], (B, L, G, Dv), jnp.bfloat16)
        do = jax.random.normal(ks[3], (B, L, H, Dv), jnp.bfloat16)
        choice = ()
        if chosen:
            choice = jax.jit(jax.vmap(lambda *i: ss.select_topk(*i, TOPK)))(
                jax.random.normal(ks[4], (B, L, 16, 64), jnp.bfloat16),
                jax.random.normal(ks[5], (B, L, 64), jnp.bfloat16),
                jax.random.uniform(ks[6], (B, L, 16)))

        def attn(q, k, v, *choice):
            keep, tiles = choice or (None, None)
            return jax.vmap(lambda q, k, v, keep, tiles: flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                window=window, keep=keep, tiles=tiles),
                in_axes=(0, 0, 0, 0 if chosen else None,
                         0 if chosen else None))(q, k, v, keep, tiles)

        def step(q, k, v, do, *choice):
            o, pull = jax.vjp(lambda q, k, v: attn(q, k, v, *choice), q, k, v)
            return o, pull(do)

        fn = jax.jit(step)
        row = {"shape": name, "batch": B, "length": L, "heads": H,
               "kv_heads": G, "head_dim": D, "v_head_dim": Dv,
               "window": window, "choice": chosen, "block": block,
               **kernel_ms(fn, q, k, v, do, *choice, calls=args.calls)}
        o = fn(q, k, v, do, *choice)[0].astype(jnp.float32)
        row["o_sum"] = float(o.sum())
        row["o_abs_sum"] = float(jnp.abs(o).sum())
        if chosen:
            row["pairs_kept"] = int(choice[0].astype(jnp.int32).sum())
            row["tiles_live"] = int(choice[1].sum())
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "root": os.path.abspath(args.root), "rows": rows}),
          flush=True)


if __name__ == "__main__":
    main()
