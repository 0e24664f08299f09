"""The attention gate's projection's share of its roofline: the least time
for the gate's ``hidden -> q heads x head size`` product of every layer over
the tokens computed (``dense_groups.py``, three passes) over the device time
of the ``matmul``-class ops under ``model.attn_gate``.  The sigmoid and the
multiply are the scope's other classes and earn nothing here
(``attn_gate_pct`` has their seconds)."""

from benchmark import dense_groups

GROUP = "attn_gate"
SCOPE = dense_groups.PREFIX + GROUP


def read(ctx):
    return dense_groups.roofline(ctx, GROUP)
