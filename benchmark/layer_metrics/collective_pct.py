"""Share of device busy time in collective ops."""


def read(ctx):
    d = ctx.summary.devices
    return 100.0 * sum(x.collective_ns for x in d) / sum(x.busy_ns for x in d)
