"""What the accepted configurations' kinds lower to, as data: the sha256 of
``jit(...).lower(...).as_text()`` of a toy round (2 clients x 2 steps x batch
2 x 32 tokens through ``make_multi_round_fn``, bf16 compute) of each family's
toy model, its parameter tree by path and shape, and the ungated
``MultiHeadAttention``'s forward and gradients.  A helper of
``test_afmoe_decoder.py`` and ``test_attention_gate.py``, which hold the
working tree to ``testdata/lowered_parent_pr40.json``: the copy taken from the
commit before the gate, the post-norms, the embedding's factor and the
selection bias came (every one off by default), with the rounds of the
families that have an expert layer taken again when that layer came to count
the rows its grouped products compute.  The compile cache's key is the
program: a key that moves costs every accepted cell a cold set-up.

To take the copy again from a checkout (after a change that means to move
the programs):

    JAX_PLATFORMS=cpu python tests/lowered_programs.py <checkout> <out.json>

and of the families that came later (``LATER``), each pinned in a copy of its
own from the commit before the next family came
(``testdata/lowered_parent_pr44.json``: ``afmoe``, held by
``test_nemotron_h_decoder.py``):

    JAX_PLATFORMS=cpu python tests/lowered_programs.py <checkout> <out.json> afmoe

and, from the commit before the state-space scan's kernels came
(``testdata/lowered_parent_pr47.json``: the ``nemotron_h`` toy, whose scan's
shape the kernels refuse, and ``ssd_chunked`` alone at such a shape, forward
and gradients; held by ``test_ssd.py``):

    JAX_PLATFORMS=cpu python tests/lowered_programs.py <checkout> <out.json> nemotron_h ssd_chunked
"""

import hashlib
import json
import os
import sys

FAMILIES = ("transformer_lm", "mellum_moe", "kimi_linear", "keye_sparse")
LATER = ("afmoe",)
NEWEST = ("nemotron_h", "ssd_chunked")


def toy_bundle(family):
    import test_model_scopes

    if family not in ("keye_sparse", "afmoe", "nemotron_h"):
        return test_model_scopes.toy_bundle(family)
    from fedml_tpu.models.decoder import decoder_lm

    if family == "afmoe":
        from test_afmoe_decoder import SHARE
    elif family == "nemotron_h":
        from test_nemotron_h_decoder import SHARE
    else:
        from test_sparse_decoder import SHARE
    return decoder_lm(SHARE)


def ssd_program() -> dict:
    """{"sha256"} of ``ssd_chunked``'s lowered value and gradients at a
    toy shape (``x`` [40, 4, 8], two groups of a 16-wide state, chunks of
    16) that the state-space kernels do not take."""
    import functools

    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.ssd import ssd_chunked

    shapes = [((40, 4, 8), jnp.float32), ((40, 4), jnp.float32),
              ((4,), jnp.float32)] + [((40, 2, 16), jnp.float32)] * 2
    args = [jax.ShapeDtypeStruct(*s) for s in shapes]
    scan = functools.partial(ssd_chunked, chunk=16)
    lowered = jax.jit(jax.value_and_grad(
        lambda *a: (scan(*a) ** 2).sum(), argnums=range(5))).lower(*args)
    return {"sha256": hashlib.sha256(lowered.as_text().encode()).hexdigest()}


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


def programs(families=()) -> dict:
    """The accepted families' programs, or those of ``families`` alone."""
    if families:
        return {family: ssd_program() if family == "ssd_chunked"
                else program_of(family) for family in families}
    import test_attention_gate

    return {"multi_head_attention_ungated":
            test_attention_gate.ungated_program(),
            **{family: program_of(family) for family in FAMILIES}}


if __name__ == "__main__":
    root, dst, *families = sys.argv[1:]
    here = os.path.dirname(os.path.abspath(__file__))
    # the checkout's program and its toys; this file's own helpers last
    sys.path[:0] = [root, os.path.join(root, "tests"), here]
    # as ``conftest.py`` sets the suite up
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    with open(dst, "w") as f:
        json.dump(programs(families), f, indent=1, sort_keys=True)
        f.write("\n")
