"""Share of device busy time that is ``while`` self time: the loops' own
bookkeeping between their bodies' ops (step scan, client ``lax.map``, round
scan)."""


def read(ctx):
    return 100.0 * ctx.summary.class_share("loop")
