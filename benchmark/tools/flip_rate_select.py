"""``flip_rate.py`` for the ``keye_sparse`` family: how many of the (query,
key) pairs that a sparse-attention layer keeps differ between the program's
choice and the plain reference's own, by layer, on one batch of the cell's
traffic with weights from the seed.  The ``topk``-th and the next index score
of a row of thousands lie close, and the program rounds its residual stream
to the compute dtype where the reference does not, so the two sets differ
near the edge; a swapped key carries about 1 / ``topk`` of a query's weight.

    chiprun -- python3 benchmark/tools/flip_rate_select.py --workload <cell> --seed <n>

``flip_share_by_layer``: pairs in one set and not in the other over the pairs
the reference keeps.  The choice is made again here, by the program's
``select_topk`` from its indexer's own output (``qI``, ``kI`` in the compute
dtype, ``w`` in float32), as the layer makes it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from benchmark import cells, traffic
    from benchmark.drivers import base
    from benchmark.families import keye_sparse_plain
    from fedml_tpu.algorithms.fedavg import resolve_compute_dtype
    from fedml_tpu.core import tree as treelib
    from fedml_tpu.ops.sparse_select import select_topk

    cell = cells.load_cell(args.workload, rehearsal=args.rehearsal)
    bundle = cells.build_bundle(cell.config)
    variables = base.seeded_state(bundle, args.seed).variables
    x, _ = traffic.make_samples(cell.config, cell.geometry["batch"],
                                args.seed)
    dtype = resolve_compute_dtype(cell.config["compute_dtype"])
    topk = cell.config["sa_config"]["topk"]
    layers = range(cell.config["n_layer"])

    @jax.jit
    def program(variables, x):
        """Every layer's keep mask [B, L, L], as the program makes it."""
        if dtype is not None:
            variables = treelib.tree_cast_floats(variables, dtype)
        _, mutated = bundle.module.apply(
            variables, x, train=True, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "indexer")
        blocks = mutated["intermediates"]

        return [jax.vmap(lambda *i: select_topk(*i, topk)[0])(
            *blocks[f"Block_{i}"]["MultiHeadAttention_0"]["indexer"]
            ["__call__"][0]) for i in layers]

    @jax.jit
    def plain(variables, x):
        with jax.default_matmul_precision("highest"):
            return keye_sparse_plain.forward(
                cell.config, variables["params"], x, with_selection=True)[1]

    theirs = [np.asarray(a) for a in plain(variables, x)]
    ours = [np.asarray(a) != 0 for a in program(variables, x)]
    flips = [float((o != t).sum() / t.sum()) for o, t in zip(ours, theirs)]

    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "device": jax.devices()[0].device_kind,
                      "pairs_kept_by_layer": [int(t.sum()) for t in theirs],
                      "flip_share_by_layer": flips,
                      "flip_share": float(np.mean(flips))}),
          flush=True)


if __name__ == "__main__":
    main()
