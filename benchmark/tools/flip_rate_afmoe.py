"""``flip_rate.py`` for the ``afmoe`` family: how often the program and
its plain reference pick different experts, by expert layer, on one batch of
the cell's traffic with weights from the seed.  Both choose the largest of
``scores + bias``; the program rounds its residual stream, the router's
weights and the bias to the compute dtype where the reference does not.  Also
prints the share of tokens the bias moved, by the program's own counter.

    chiprun -- python3 benchmark/tools/flip_rate_afmoe.py --workload <cell> --seed <n>
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
    from benchmark.families import afmoe_plain
    from fedml_tpu.algorithms.fedavg import resolve_compute_dtype
    from fedml_tpu.core import tree as treelib
    from fedml_tpu.models.base import COUNTERS
    from fedml_tpu.models.decoder import TOKENS_BIAS_MOVED

    cell = cells.load_cell(args.workload, rehearsal=args.rehearsal)
    bundle = cells.build_bundle(cell.config)
    variables = base.seeded_state(bundle, args.seed).variables
    x, _ = traffic.make_samples(cell.config, cell.geometry["batch"],
                                args.seed)
    dtype = resolve_compute_dtype(cell.config["compute_dtype"])
    sparse = [i for i, (_, mlp) in enumerate(
        afmoe_plain.layer_kinds(cell.config)) if mlp == "sparse"]

    @jax.jit
    def program(variables, x):
        if dtype is not None:
            variables = treelib.tree_cast_floats(variables, dtype)
        _, mutated = bundle.module.apply(
            variables, x, train=True, mutable=["intermediates", COUNTERS])
        blocks = mutated["intermediates"]
        return [blocks[f"Block_{i}"]["ExpertLayer_0"]["top_e"][0]
                for i in sparse], mutated[COUNTERS][TOKENS_BIAS_MOVED]

    @jax.jit
    def plain(variables, x):
        with jax.default_matmul_precision("highest"):
            return afmoe_plain.forward(
                cell.config, variables["params"], x, with_selection=True)[1]

    ours, moved = program(variables, x)
    ours = [np.asarray(a) for a in ours]
    theirs = [np.asarray(a) for a in plain(variables, x)]
    flips = [float(np.mean([(o[:, s:s + 1] != t).all(axis=1)
                            for s in range(o.shape[1])]))
             for o, t in zip(ours, theirs)]
    held = cell.config["experts_held"]
    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "device": jax.devices()[0].device_kind,
                      "expert_layers": sparse,
                      "flip_rate_by_layer": flips,
                      "flip_rate": float(np.mean(flips)),
                      "bias_moved_pct": 100.0 * float(moved) / (
                          x.size * len(sparse)),
                      "held_assignments_by_layer": [
                          int(np.isin(o, held).sum()) for o in ours]}),
          flush=True)


if __name__ == "__main__":
    main()
