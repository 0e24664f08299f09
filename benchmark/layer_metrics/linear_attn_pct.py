"""Share of device busy time in the linear-attention layers: every op under
a ``model.kda_*`` scope (``fedml_tpu/obs/scopes.py``: projections and
convolutions, gates, the chunked scan, the gated norm and output projection),
forward and backward.  A cut across the forward/backward partition, inside
``fed.model``.  Nothing where no op of the trace carries such a scope (a
program without the layer)."""

from benchmark import fed_scopes

KDA = "model.kda_"


def in_layer(op) -> bool:
    return KDA in fed_scopes.tf_op(op)


def read(ctx):
    seconds = ctx.summary.seconds_where(in_layer)
    if not seconds:
        return None
    return 100.0 * seconds / ctx.summary.busy_s
