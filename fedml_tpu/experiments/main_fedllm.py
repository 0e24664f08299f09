"""Entry shim — federated transformer fine-tuning (beyond-reference
long-context family; ``--mesh dp,mp`` runs DP x TP on a (dp, mp)
device mesh)."""

import sys

if __package__ in (None, ""):  # run as a script: _bootstrap fixes sys.path
    import _bootstrap  # noqa: F401

from fedml_tpu.experiments.run import main

if __name__ == "__main__":
    main([
        "--algorithm", "fedllm", "--dataset", "fed_shakespeare",
        *sys.argv[1:],
    ])
