"""How unevenly the router loads the experts held: the fullest expert's rows
over the mean, ``experts held x moe_expert_tokens_max / moe_assignments_held``
of the traced calls (both counters are sums over layers, steps and clients).
1 is level; the grouped products' time follows the fullest expert only where
experts run side by side."""

from benchmark.layer_metrics.expert_matmul_roofline import assignments

COUNTER = "moe_expert_tokens_max"


def read(ctx):
    made = assignments(ctx)
    if not made or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    fullest = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    return ctx.cell.config["num_experts"] * fullest / made
