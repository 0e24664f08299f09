"""Share of device busy time in the expert layer: every op under a
``model.moe_*`` scope (``fedml_tpu/obs/scopes.py``: router, dispatch, the
grouped products, combine), forward and backward.  A cut across the
forward/backward partition, inside ``fed.model``.  Nothing where no op of
the trace carries such a scope (a program without the layer)."""

from benchmark import fed_scopes

MOE = "model.moe_"
EXPERTS = "model.moe_experts"  # the grouped products and the gate between


def in_layer(op) -> bool:
    return MOE in fed_scopes.tf_op(op)


def read(ctx):
    seconds = ctx.summary.seconds_where(in_layer)
    if not seconds:
        return None
    return 100.0 * seconds / ctx.summary.busy_s
