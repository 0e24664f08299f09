"""Share of device busy time in collectives while no compute op runs on
that device."""


def read(ctx):
    d = ctx.summary.devices
    return (100.0 * sum(x.collective_exposed_ns for x in d)
            / sum(x.busy_ns for x in d))
