"""Share of device busy time inside the attention function (scores,
softmax, values; not the qkv and output projections)."""


def read(ctx):
    return 100.0 * ctx.summary.class_share("attention")
