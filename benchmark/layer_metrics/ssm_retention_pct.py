"""How much of a head's state the Mamba-2 mixers keep over a token, on
average: ``100 x exp(ssm_log_decay_mean / layer-steps)`` of the traced calls.
The counter is each layer's mean of ``dt A`` (the log decay) over tokens and
heads, summed over layers, steps and clients, so dividing by the layer-steps
gives the mean log decay and its ``exp`` the geometric mean of the decay.  It
says which numeric regime the scan was timed in: near 0 the state is wiped
every token, near 100 it carries across whole chunks.  Nothing where the
program has no such counter."""

import math

from benchmark import cells

COUNTER = "ssm_log_decay_mean"


def read(ctx):
    family = cells.load_family(ctx.cell.config)
    if not ctx.calls or not all(COUNTER in c[3] for c in ctx.calls) \
            or not hasattr(family, "layer_counts"):
        return None
    total = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    layer_steps = (sum(c[2] for c in ctx.calls)
                   * family.layer_counts(ctx.cell.config)["mamba"]
                   * ctx.session.padded_samples_per_round()
                   / ctx.cell.geometry["batch"])
    return 100.0 * math.exp(total / layer_steps)
