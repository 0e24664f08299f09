"""Share of device busy time in the gate of a gated attention: the ops whose
innermost ``model.*`` scope is ``model.attn_gate`` (``fedml_tpu/obs/
scopes.py``: the gate's projection of the layer's input, its sigmoid and the
multiply of the attention function's output before the output projection),
forward and backward.  A cut across the forward/backward partition, inside
``fed.model``.  Nothing where no op carries the scope (a program without the
gate)."""

from benchmark import model_scopes

SCOPE = "model.attn_gate"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
