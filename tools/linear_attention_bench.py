"""The gated delta rule alone, on the chip: ``gated_delta_rule_lax`` (lax
ops, differentiated by JAX) and ``gated_delta_rule_kernels`` (the Pallas
kernels with their hand-written backwards) of
``fedml_tpu/ops/linear_attention.py`` at the shape of the cell
``kimilin_silo_doc8k``: [8192, 4, 128], chunk 64, ``v`` in bf16, under the
layer's ``vmap`` over a batch of one, with ``g`` the log of a retention drawn
in 0.92-0.97 a channel and token (the cell's ``linear_attn_retention_pct``).

    chiprun -- python3 tools/linear_attention_bench.py [--calls 20] [--ops]

One JSON line: milliseconds a call, forward and forward + backward (``--calls``
of them inside one jitted loop, so that no dispatch is timed; each call's ``q``
takes one element of the call before), and the largest difference between the
two forms' ``o`` and gradients, relative to the lax form's largest entry.
``--ops`` adds, from a profile of one forward + backward of the kernel path,
the device milliseconds of each op that takes 1 % or more.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

L, H, D, CHUNK = 8192, 4, 128, 64
RETENTION = (0.92, 0.97)


def device_ops_ms(fn, *args):
    """{op name: device milliseconds} of one call of the jitted ``fn``."""
    import jax

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready(fn(*args))
        path, = glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(path)
    ops = collections.Counter()
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                ops[event.name] += event.duration_ns / 1e6
    return dict(ops)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import linear_attention as la

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(keys[i], (1, L, H, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / D ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = v.astype(jnp.bfloat16)
    g = jnp.log(jax.random.uniform(keys[3], (1, L, H, D), minval=RETENTION[0],
                                   maxval=RETENTION[1]))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, L, H)))
    do = jax.random.normal(keys[5], (1, L, H, D), jnp.bfloat16)
    operands = (q, k, v, g, beta)

    def forward(form):
        return jax.vmap(lambda *a: form(*a, chunk=CHUNK))

    def both(form):  # (o, the five gradients)
        def call(*operands):
            o, back = jax.vjp(forward(form), *operands)
            return (o, *back(do))
        return call

    def ms(fn):
        """(milliseconds a call of ``fn(*operands)``, its results)."""
        def chained(q, *rest):
            def call(_, carry):
                q, out = carry
                first = jax.tree_util.tree_leaves(out)[0]
                q = q.at[0, 0, 0, 0].add(1e-9 * first[0, 0, 0, 0].astype(q.dtype))
                return q, fn(q, *rest)
            return jax.lax.fori_loop(0, args.calls, call,
                                     (q, fn(q, *rest)))[1]

        out = jax.block_until_ready(jax.jit(fn)(*operands))
        loop = jax.jit(chained)
        jax.block_until_ready(loop(*operands))
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*operands))
        return (time.perf_counter() - t0) / (args.calls + 1) * 1e3, out

    result = {"device": jax.devices()[0].device_kind, "calls": args.calls,
              "shape": [L, H, D], "chunk": CHUNK}
    outs = {}
    for name, form in (("lax", la.gated_delta_rule_lax),
                       ("kernels", la.gated_delta_rule_kernels)):
        result[f"{name}_forward_ms"], _ = ms(forward(form))
        result[f"{name}_forward_backward_ms"], outs[name] = ms(both(form))
    for part, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                          outs["kernels"], outs["lax"]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        result[f"{part}_max_diff_rel"] = float(
            jnp.abs(a - b).max() / jnp.abs(b).max())
    if args.ops:
        ops = device_ops_ms(jax.jit(both(la.gated_delta_rule_kernels)),
                            *operands)
        total = sum(ops.values())
        result["kernels_ops_ms"] = {
            n: round(t, 4) for n, t in sorted(ops.items(), key=lambda x: -x[1])
            if t >= 0.01 * total}
        result["kernels_ops_total_ms"] = round(total, 4)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
