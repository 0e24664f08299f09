"""Share of device busy time in dense matmuls (projections, MLP, head)."""


def read(ctx):
    return 100.0 * ctx.summary.class_share("matmul")
