"""The Mamba-2 scan alone, on the chip: ``ssd_chunked_lax`` (lax ops,
differentiated by JAX) and ``ssd_kernels`` (the Pallas kernels with their
hand-written backward) of ``fedml_tpu/ops/ssd.py`` at the shape of the cell
``nemo3super_silo_agent8k``: ``x`` [8192, 16, 64] and ``B``, ``C`` [8192, 1,
128] in bf16, chunk 128, under the layer's ``vmap`` over a batch of one, with
``dt`` drawn log-uniform in Mamba-2's initial range [1e-3, 1e-1] and
``A = -U[1, 16]`` (its ``A_log`` initialisation).

    python3 tools/ssd_bench.py [--calls 20] [--ops]    # on a TPU host

One JSON line: milliseconds a call, forward and forward + backward (``--calls``
of them inside one jitted loop, so that no dispatch is timed; each call's ``x``
takes one element of the call before), and the largest difference between the
two forms' ``y`` and gradients, relative to the lax form's largest entry.
``--ops`` adds, from a profile of one forward + backward of the kernel path,
the device milliseconds of each op that takes 1 % or more.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.linear_attention_bench import device_ops_ms  # noqa: E402

L, H, P, G, N, CHUNK = 8192, 16, 64, 1, 128, 128


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import ssd

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (1, L, H, P), bf16)
    dt = jnp.exp(jax.random.uniform(keys[1], (1, L, H), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    A = -jax.random.uniform(keys[2], (H,), minval=1.0, maxval=16.0)
    B, C = (jax.random.normal(keys[i], (1, L, G, N), bf16) for i in (3, 4))
    dy = jax.random.normal(keys[5], (1, L, H, P), bf16)
    operands = (x, dt, A, B, C)

    def forward(form):
        return lambda x, dt, A, B, C: jax.vmap(
            lambda x, dt, B, C: form(x, dt, A, B, C, chunk=CHUNK))(x, dt, B, C)

    def both(form):  # (y, the five gradients)
        def call(*operands):
            y, back = jax.vjp(forward(form), *operands)
            return (y, *back(dy))
        return call

    def ms(fn):
        """(milliseconds a call of ``fn(*operands)``, its results)."""
        def chained(x, *rest):
            def call(_, carry):
                x, out = carry
                first = jax.tree_util.tree_leaves(out)[0]
                x = x.at[0, 0, 0, 0].add(
                    1e-9 * first[0, 0, 0, 0].astype(x.dtype))
                return x, fn(x, *rest)
            return jax.lax.fori_loop(0, args.calls, call,
                                     (x, fn(x, *rest)))[1]

        out = jax.block_until_ready(jax.jit(fn)(*operands))
        loop = jax.jit(chained)
        jax.block_until_ready(loop(*operands))
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*operands))
        return (time.perf_counter() - t0) / (args.calls + 1) * 1e3, out

    result = {"device": jax.devices()[0].device_kind, "calls": args.calls,
              "shape": [L, H, P, G, N], "chunk": CHUNK}
    outs = {}
    for name, form in (("lax", ssd.ssd_chunked_lax),
                       ("kernels", ssd.ssd_kernels)):
        result[f"{name}_forward_ms"], _ = ms(forward(form))
        result[f"{name}_forward_backward_ms"], outs[name] = ms(both(form))
    for part, a, b in zip(("y", "dx", "ddt", "dA", "dB", "dC"),
                          outs["kernels"], outs["lax"]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        result[f"{part}_max_diff_rel"] = float(
            jnp.abs(a - b).max() / jnp.abs(b).max())
    if args.ops:
        ops = device_ops_ms(jax.jit(both(ssd.ssd_kernels)), *operands)
        total = sum(ops.values())
        result["kernels_ops_ms"] = {
            n: round(t, 4) for n, t in sorted(ops.items(), key=lambda x: -x[1])
            if t >= 0.01 * total}
        result["kernels_ops_total_ms"] = round(total, 4)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
