"""How many tokens the router's selection bias moved: ``100 x
moe_tokens_bias_moved`` of the traced calls ``/`` (tokens computed x expert
layers).  The counter is each expert layer's count of tokens whose chosen set
is not the ``top_k`` largest of the scores alone, summed over layers, steps
and clients.  A diagnostic: it moves with the seed's bias and the router's
numerics, reads 0 where the mechanism is dead (a zero bias) and says which
regime the router was timed in.  Nothing where the program has no such
counter."""

from benchmark import cells
from benchmark.layer_metrics.step_mfu_pct import computed_units

COUNTER = "moe_tokens_bias_moved"


def read(ctx):
    if not ctx.calls or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    layers = cells.load_family(ctx.cell.config).layer_counts(
        ctx.cell.config)["sparse"]
    moved = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    return 100.0 * moved / (computed_units(ctx) * layers)
