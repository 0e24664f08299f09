"""What the check's two limits can tell apart in a cell of the
``nemotron_h`` family: the cell's own comparison (``run.py:check_reference``:
one round of the program on the reduced cohort against
``reference.reference_round``, put through ``reference.compare``) with the
plain reference swapped for a control that ought to come out as not correct.
The program's round runs once; every control is one more reference round
from the same state, and prints one line: the numbers ``correct`` compares
beside their limits, and the same relative error by kind of leaf (a leaf's
layers summed), which ``compare`` does not look at.

  sound          the float32 reference as the cell runs it
  ref_bf16       the reference computed in bfloat16 throughout: no float32
                 decays, norms, router or sums between ops
  skip_dropped   the reference's Mamba mixers without the ``D x`` skip (its
                 ``D`` read as zero)

    python3 benchmark/tools/control_nemotron_h.py --workload <cell> --seed <n>

on a TPU, or with ``--rehearsal`` at the workload's toy geometry on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools.control_afmoe import by_leaf_kind, zeroed  # noqa: E402

# name -> (the reference's dtype, the leaf it reads as zero)
CONTROLS = {
    "sound": ("float32", None),
    "ref_bf16": ("bfloat16", None),
    "skip_dropped": ("float32", "Mamba2Mixer_0/D"),
}


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
    from benchmark.families import nemotron_h_plain
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
            nemotron_h_plain.PlainBundle(cell.config, dtype), cell.config,
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
