"""The vocabulary head's share of its roofline: the least time the chip
could take for its three products over the tokens computed
(``dense_groups.py``: 3 x 2 x width x vocabulary FLOPs a token, FLOP-bound)
over the device time of the ``matmul``-class ops under ``model.head``."""

from benchmark import dense_groups

GROUP = "head"
SCOPE = dense_groups.PREFIX + GROUP


def read(ctx):
    return dense_groups.roofline(ctx, GROUP)
