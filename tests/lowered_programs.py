"""What the accepted configurations' kinds lower to, as data: the sha256 of
``jit(...).lower(...).as_text()`` of a toy round (2 clients x 2 steps x batch
2 x 32 tokens through ``make_multi_round_fn``, bf16 compute) of each family's
toy model, its parameter tree by path and shape, and the ungated
``MultiHeadAttention``'s forward and gradients.  A helper of
``test_afmoe_decoder.py`` and ``test_attention_gate.py``, which hold the
working tree to ``testdata/lowered_parent_pr40.json``: the copy taken from the
commit before the gate, the post-norms, the embedding's factor and the
selection bias came (every one off by default).  The compile cache's key is
the program: a key that moves costs every accepted cell a cold set-up.

To take the copy again from a checkout (after a change that means to move
the programs):

    JAX_PLATFORMS=cpu python tests/lowered_programs.py <checkout> <out.json>
"""

import hashlib
import json
import os
import sys

FAMILIES = ("transformer_lm", "mellum_moe", "kimi_linear", "keye_sparse")


def toy_bundle(family):
    import test_model_scopes

    if family != "keye_sparse":
        return test_model_scopes.toy_bundle(family)
    from fedml_tpu.models.decoder import decoder_lm
    from test_sparse_decoder import SHARE

    return decoder_lm(SHARE)


def program_of(family) -> dict:
    """{"round_sha256", "tree"} of the family's toy round and model."""
    import jax
    import test_model_scopes

    variables, lowered = test_model_scopes.lower_round(toy_bundle(family))
    return {
        "round_sha256": hashlib.sha256(
            lowered.as_text().encode()).hexdigest(),
        "tree": ["/".join(k.key for k in path) + " "
                 + "x".join(map(str, a.shape))
                 for path, a in jax.tree_util.tree_flatten_with_path(
                     variables["params"])[0]]}


def programs() -> dict:
    import test_attention_gate

    return {"multi_head_attention_ungated":
            test_attention_gate.ungated_program(),
            **{family: program_of(family) for family in FAMILIES}}


if __name__ == "__main__":
    root, dst = sys.argv[1:3]
    here = os.path.dirname(os.path.abspath(__file__))
    # the checkout's program and its toys; this file's own helpers last
    sys.path[:0] = [root, os.path.join(root, "tests"), here]
    # as ``conftest.py`` sets the suite up
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    with open(dst, "w") as f:
        json.dump(programs(), f, indent=1, sort_keys=True)
        f.write("\n")
