"""The expert layer's row transfers alone, on the chip: ``to_buffer`` and
``from_buffer`` (``fedml_tpu/ops/expert_rows.py``: the kernel and the lax
form) and the routing weights' gradient as a gather of ``T x k`` scalars or a
scatter of ``C``, at the shapes of the two decoder cells (8192 tokens, top 8,
h 2304 in bf16, 8 experts held of 64 with a 16,384-row buffer and of 256 with
a 4,096-row one) under a level router: about one held slot a token, none on a
third of them.  Rows of the buffer past the routed count hold NaN, as a
grouped product may leave them.

    chiprun -- python3 tools/expert_rows_bench.py [--window 64 128] [--skew 9]

One JSON line: milliseconds a call (``--calls`` of them inside one jitted
loop, so that no dispatch is timed; each call's first operand takes one
element of the call before), and the kernel's largest difference from the lax
form.  ``--skew`` adds to the first held expert's scores: with 9 it is in
every token's top-k and its range of a token tile is four windows long.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T, K, H, HELD = 8192, 8, 2304, 8
SHAPES = {"mellum2_silo_code8k": (64, 16384), "kimilin_silo_doc8k": (256, 4096)}


def level_route(key, routed, capacity, skew=0.0):
    """The ``Route`` of a router with random scores over ``routed`` experts
    (``skew`` added to the first's), the first ``HELD`` held, and the sort's
    ``order``."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.expert_rows import sorted_route

    scores = jax.random.normal(key, (T, routed)).at[:, 0].add(skew)
    _, top_e = jax.lax.top_k(scores, K)
    local = jnp.minimum(top_e, HELD).astype(jnp.int32)
    order = jnp.argsort(local.reshape(T * K), stable=True)
    rank = jnp.argsort(order).reshape(T, K)
    sizes = (local.reshape(-1, 1) == jnp.arange(HELD)).sum(0, dtype=jnp.int32)
    if int(sizes.sum()) > capacity:
        return None, None
    return (sorted_route(local, order, rank, sizes, capacity),
            order[:capacity])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--window", type=int, nargs="*", default=[])
    ap.add_argument("--skew", type=float, default=0.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import expert_rows as er

    def ms(fn, first, *rest):
        """(milliseconds a call of ``fn(first, *rest)``, its result)."""
        def chained(first, *rest):
            def call(_, carry):
                first, out = carry
                return jax.lax.dynamic_update_slice(
                    first, out.reshape(-1)[:1].reshape((1,) * first.ndim
                                                       ).astype(first.dtype),
                    (0,) * first.ndim), fn(first, *rest)
            return jax.lax.fori_loop(0, args.calls, call,
                                     (first, fn(first, *rest)))[1]

        out = jax.block_until_ready(jax.jit(fn)(first, *rest))
        loop = jax.jit(chained)
        jax.block_until_ready(loop(first, *rest))
        t0 = time.perf_counter()
        jax.block_until_ready(loop(first, *rest))
        return (time.perf_counter() - t0) / (args.calls + 1) * 1e3, out

    result = {"device": jax.devices()[0].device_kind, "calls": args.calls,
              "skew": args.skew}
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    for cell, (routed, capacity) in SHAPES.items():
        route, order = level_route(keys[0], routed, capacity, args.skew)
        if route is None:
            result[cell] = "the routed rows do not fit the buffer"
            continue
        buf = jnp.where(route.row_live[:, None],
                        jax.random.normal(keys[1], (capacity, H)),
                        jnp.nan).astype(jnp.bfloat16)
        dy = jax.random.normal(keys[2], (T, H), jnp.bfloat16)
        d_row_w = jax.random.normal(keys[3], (capacity,))
        held_slots = route.here.sum(axis=1)
        row = {"routed_rows": int(route.group_sizes.sum()),
               "tokens_with_no_held_slot": int((held_slots == 0).sum()),
               "most_held_slots_a_token": int(held_slots.max())}

        row["to_buffer_ms"], _ = ms(er.to_buffer, dy, route)
        row["gather_alone_ms"], _ = ms(lambda dy, route: dy[route.tok], dy, route)
        # the form this replaced: dy broadcast over the k slots into T x k
        # rows, and the buffer's rows gathered back out of that
        row["to_buffer_through_pairs_ms"], _ = ms(
            lambda dy, route, order: jnp.where(
                route.row_live[:, None], jnp.where(
                    route.here[..., None], dy[:, None, :], 0
                ).reshape(T * K, H)[order], 0), dy, route, order)
        row["from_buffer_lax_ms"], want = ms(er.from_buffer_lax, buf, route)
        window = er.window_rows(T, capacity, H, HELD, 2)
        for w in args.window or [window]:
            name = f"from_buffer_windows_w{w}"
            try:
                row[name + "_ms"], got = ms(
                    lambda b, r, w=w: er.from_buffer_windows(b, r, w),
                    buf, route)
                row[name + "_max_abs_diff"] = float(jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32)
                ).max())
            except Exception as e:  # a refusal is an answer too
                row[name + "_error"] = str(e).splitlines()[0][:300]
        # the routing weights' gradient, d_top_p[t, j] = d_row_w[rank[t, j]]
        row["weights_grad_gather_ms"], a = ms(
            lambda g, route: jnp.where(route.here, g[route.rank], 0),
            d_row_w, route)
        row["weights_grad_scatter_ms"], b = ms(
            lambda g, route, order: jnp.zeros((T * K,), g.dtype).at[order].add(
                jnp.where(route.row_live, g, 0), unique_indices=True
            ).reshape(T, K), d_row_w, route, order)
        row["weights_grad_max_abs_diff"] = float(jnp.abs(a - b).max())
        result[cell] = row
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
