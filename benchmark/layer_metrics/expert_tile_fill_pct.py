"""How much of what the expert layer's grouped products compute was routed:
``100 x moe_assignments_held / moe_rows_computed`` of the traced calls (both
counters are sums over layers, steps and clients).  The rows computed are
the row tiles the kernels' grid visits times the tile
(``fedml_tpu/models/decoder.py`` ``rows_computed``): a group that starts
inside a tile shares it with the group before and the tile is computed for
each, and a group's last tile is computed whole.  Where every group starts
on a tile only the last tile's unused rows are lost.  A layer-step that
falls back to every expert over every token computes ``tokens x experts
held`` rows.  Nothing where the program has no such counter."""

from benchmark.layer_metrics.expert_matmul_roofline import assignments

COUNTER = "moe_rows_computed"


def read(ctx):
    made = assignments(ctx)
    if not made or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    computed = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    return 100.0 * made / computed if computed else None
