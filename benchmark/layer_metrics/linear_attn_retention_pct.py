"""How much of a state channel of the linear-attention layers survives a
token, on average: ``100 x exp(kda_log_decay_mean / layer-steps)`` of the
traced calls.  The counter is each layer's mean log decay over tokens, heads
and channels, summed over layers, steps and clients, so dividing by the
layer-steps gives the mean log decay and its ``exp`` the geometric mean of the
decay.  It says which numeric regime the scan was timed in: near 0 the state is
wiped every token and a chunk is trivially stable, near 100 it is not.  Nothing
where the program has no such counter."""

import math

from benchmark import cells

COUNTER = "kda_log_decay_mean"


def read(ctx):
    family = cells.load_family(ctx.cell.config)
    if not ctx.calls or not all(COUNTER in c[3] for c in ctx.calls) \
            or not hasattr(family, "layer_counts"):
        return None
    total = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    layer_steps = (sum(c[2] for c in ctx.calls)
                   * family.layer_counts(ctx.cell.config)["kda"]
                   * ctx.session.padded_samples_per_round()
                   / ctx.cell.geometry["batch"])
    return 100.0 * math.exp(total / layer_steps)
