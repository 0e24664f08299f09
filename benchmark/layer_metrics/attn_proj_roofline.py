"""The attention projections' share of their roofline: the least time for
the fused q/k/v product and the output product of every
``MultiHeadAttention`` over the tokens computed (``dense_groups.py``) over
the device time of the ``matmul``-class ops under ``model.attn_proj``."""

from benchmark import dense_groups

GROUP = "attn_proj"
SCOPE = dense_groups.PREFIX + GROUP


def read(ctx):
    return dense_groups.roofline(ctx, GROUP)
