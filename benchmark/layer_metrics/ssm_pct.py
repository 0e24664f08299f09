"""Share of device busy time in the Mamba-2 mixers: every op under a
``model.ssm_*`` scope (``fedml_tpu/obs/scopes.py``: in_proj, the
convolution, SiLU, the splits and dt's softplus; the chunked scan; the skip,
the gated norm and out_proj), forward and backward.  A cut across the
forward/backward partition, inside ``fed.model``.  Nothing where no op of the
trace carries such a scope (a program without the mixer)."""

from benchmark import fed_scopes

SSM = "model.ssm_"


def in_layer(op) -> bool:
    return SSM in fed_scopes.tf_op(op)


def read(ctx):
    seconds = ctx.summary.seconds_where(in_layer)
    if not seconds:
        return None
    return 100.0 * seconds / ctx.summary.busy_s
