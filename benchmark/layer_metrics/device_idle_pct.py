"""Share of the traced window in which no op runs, on the device that idles
most."""


def read(ctx):
    return 100.0 * ctx.summary.idle_share()
