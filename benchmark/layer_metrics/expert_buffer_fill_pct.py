"""How full the expert layer's row buffer is: ``100 x moe_assignments_held /
moe_rows_buffered`` of the traced calls (both counters are sums over layers,
steps and clients).  The buffer's length is the capacity of the path each
layer took (``fedml_tpu/models/decoder.py`` ``buffer_capacities``), so a
layer-step that falls back to the worst case pulls the fill down: at twice
a level router's share the short buffer reads about 50, the worst case of a
chip that holds an eighth of the experts 12.5.  Nothing where the program
has no such counter."""

from benchmark.layer_metrics.expert_matmul_roofline import assignments

COUNTER = "moe_rows_buffered"


def read(ctx):
    made = assignments(ctx)
    if not made or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    buffered = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    return 100.0 * made / buffered if buffered else None
