"""What the check's two limits can tell apart in a cell of the ``afmoe``
family: the cell's own comparison (``run.py:check_reference``: one round of
the program on the reduced cohort against ``reference.reference_round``, put
through ``reference.compare``) with the plain reference swapped for a control
that ought to come out as not correct.  The program's round runs once; every
control is one more reference round from the same state, and prints one line:
the numbers ``correct`` compares beside their limits, and the same relative
error by kind of leaf (a leaf's layers summed), which ``compare`` does not
look at.

  sound         the float32 reference as the cell runs it
  ref_bf16      the reference computed in bfloat16 throughout: no float32
                router, gate, norms or sums between ops
  bias_ignored  the reference's router chooses without the selection bias
  gate_ignored  the reference's gate projection is zero: every attention
                output is halved, whatever the layer's input

    chiprun -- python3 benchmark/tools/control_afmoe.py --workload <cell> --seed <n>
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def zeroed(variables, leaf: str):
    """``variables`` with every leaf whose path ends in ``leaf`` zero, and
    every other leaf the same array."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if jax.tree_util.keystr(path, simple=True, separator="/").endswith(
            leaf) else a, variables)


# name -> (the reference's dtype, the leaf it reads as zero)
CONTROLS = {
    "sound": ("float32", None),
    "ref_bf16": ("bfloat16", None),
    "bias_ignored": ("float32", "selection_bias"),
    "gate_ignored": ("float32", "gate/kernel"),
}


def by_leaf_kind(old, new, ref) -> dict:
    """{a leaf's path without its layer: relative L2 error of the system's
    delta against the reference's, over that leaf in every layer}; a leaf
    the reference leaves where it was (the selection bias) has none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def sums(old, new, ref):
        f32 = lambda t: [jnp.asarray(l, jnp.float32)  # noqa: E731
                         for l in jax.tree_util.tree_leaves(t)]
        return [(jnp.sum(((n - o) - r) ** 2), jnp.sum(r ** 2))
                for n, o, r in zip(f32(new), f32(old), f32(ref))]

    paths = [re.sub(r"^params/(Block_\d+/)?", "", jax.tree_util.keystr(
        p, simple=True, separator="/"))
        for p, _ in jax.tree_util.tree_leaves_with_path(ref)]
    err, size = {}, {}
    for path, (e, s) in zip(paths, sums(old, new, ref)):
        err[path] = err.get(path, 0.0) + float(e)
        size[path] = size.get(path, 0.0) + float(s)
    return {path: float(np.sqrt(err[path] / size[path]))
            for path in err if size[path]}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--controls", default=",".join(CONTROLS))
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from benchmark import cells, reference
    from benchmark.families import afmoe_plain
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(min_compile_secs=0.0)
    cell = cells.load_cell(args.workload, rehearsal=args.rehearsal)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, args.seed, jax.devices()[:cell.chips])
    block = reference.reference_block(cell.config, cell.reference, args.seed)
    old = session.state
    new_vars, metrics = session.reference_round(block)
    new_vars = jax.device_get(new_vars)  # the reference needs the room
    sys_loss = float(np.sum(metrics["loss_sum"]) / np.sum(metrics["count"]))
    for name in args.controls.split(","):
        dtype, leaf = CONTROLS[name]
        ref_delta, ref_loss = reference.reference_round(
            afmoe_plain.PlainBundle(cell.config, dtype), cell.config,
            zeroed(old.variables, leaf) if leaf else old.variables, old.key,
            session.round_idx(), block)
        line = {"cell": cell.name, "seed": args.seed, "control": name,
                "device": jax.devices()[0].device_kind,
                **reference.compare(old.variables, new_vars, ref_delta,
                                    sys_loss, ref_loss),
                "delta_rel_l2_by_leaf_kind": by_leaf_kind(
                    old.variables, new_vars, ref_delta)}
        del ref_delta
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
