"""Share of the traced window in which no op runs on ANY device: the host
(dispatch, pack, readback) holds every chip back.  On one chip it equals
``device_idle_pct``; on four, the difference is chips waiting for each
other.  ``breakdown.idle_gaps`` says what the host was doing."""


def read(ctx):
    return 100.0 * ctx.summary.all_idle_share()
