"""Of the expert layer's device time (``expert_pct``), the share outside
``model.moe_experts``: router, top-k, grouping by expert, the gather of rows,
their way back to tokens and the weighting.  What the layer spends on moving
tokens rather than on its matrix products."""

from benchmark import fed_scopes
from benchmark.layer_metrics.expert_pct import EXPERTS, in_layer


def read(ctx):
    layer = ctx.summary.seconds_where(in_layer)
    if not layer:
        return None
    moving = ctx.summary.seconds_where(
        lambda op: in_layer(op) and EXPERTS not in fed_scopes.tf_op(op))
    return 100.0 * moving / layer
