"""Which grouped matrix product the expert layer should call: ``lax.ragged_dot``
against ``megablox.gmm`` on the chip, forward and backward, at the shapes of
one expert projection of a configuration (groups of about ``tokens x top_k /
routed`` rows in a worst-case buffer).

    chiprun -- python3 benchmark/tools/grouped_dot_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmark import cells

    config = cells.read_json("configs", "mellum2-12b-a2.5b.json")
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held, tokens = config["num_experts"], config["n_positions"]
    rows = tokens * min(config["num_experts_per_tok"], held)
    rng = np.random.default_rng(0)
    sizes = rng.multinomial(tokens, [1 / held] * held).astype(np.int32)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, h), jnp.bfloat16)
    w = jax.random.normal(key, (held, h, f), jnp.bfloat16) * 0.02
    gs = jnp.asarray(sizes)
    live = (jnp.arange(rows) < sizes.sum())[:, None]

    def loss(dot):
        def fn(x, w, gs):
            y = dot(x, w, gs)
            return jnp.where(live, y, 0).astype(jnp.float32).sum()
        return fn

    def tiled(*tiling):
        return lambda a, b, g: gmm(a, b, g, jnp.bfloat16, tiling)

    # group sizes are an argument: as constants the compiler folds them, and
    # the program's are computed from the router
    impls = {
        "ragged_dot": jax.lax.ragged_dot,
        "gmm_128_128_128": tiled(128, 128, 128),
        "gmm_512_1152_896": tiled(512, 1152, 896),
        "gmm_512_768_896": tiled(512, 768, 896),
        "gmm_256_1152_896": tiled(256, 1152, 896),
        "gmm_1024_1152_896": tiled(1024, 1152, 896),
    }
    flops = 2 * int(sizes.sum()) * h * f
    out = {"device": jax.devices()[0].device_kind, "rows": rows,
           "routed_rows": int(sizes.sum()), "group_sizes": sizes.tolist()}
    for name, dot in impls.items():
        try:
            fwd = jax.jit(lambda a, b, g, d=dot: jnp.where(live, d(a, b, g), 0))
            bwd = jax.jit(jax.grad(loss(dot), argnums=(0, 1)))
            times = {}
            for tag, fn, mult in (("fwd", fwd, 1), ("fwd_bwd", bwd, 3)):
                jax.block_until_ready(fn(x, w, gs))
                t0 = time.perf_counter()
                for _ in range(10):
                    r = fn(x, w, gs)
                jax.block_until_ready(r)
                dt = (time.perf_counter() - t0) / 10
                times[tag + "_ms"] = round(dt * 1e3, 3)
                times[tag + "_tflops"] = round(mult * flops / dt / 1e12, 1)
            out[name] = times
        except Exception as e:  # one that does not compile is an answer too
            out[name] = {"error": str(e).splitlines()[0][:300]}
    y0 = jax.jit(lambda a, b, g: jnp.where(
        live, jax.lax.ragged_dot(a, b, g), 0))(x, w, gs)
    y1 = jax.jit(lambda a, b, g: jnp.where(
        live, tiled(512, 1152, 896)(a, b, g), 0))(x, w, gs)
    out["max_abs_diff"] = float(jnp.abs(y0.astype(jnp.float32)
                                        - y1.astype(jnp.float32)).max())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
