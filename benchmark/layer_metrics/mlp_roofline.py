"""The dense MLP's share of its roofline: the least time for the products of
every layer without experts (two in ``Block``, three in ``GatedMLP``) over
the tokens computed (``dense_groups.py``) over the device time of the
``matmul``-class ops under ``model.mlp_dense``."""

from benchmark import dense_groups

GROUP = "mlp_dense"
SCOPE = dense_groups.PREFIX + GROUP


def read(ctx):
    return dense_groups.roofline(ctx, GROUP)
