"""Share of device busy time in ops that carry a ``fed.*`` scope.  The rest
is what the compiler made itself and hung on no stage (copies, async
starts).  0, not nothing, on a trace without scopes: that is an executable
the compile cache kept from before the program named its stages, and the
stage metrics are then absent; empty the cache once."""

from benchmark import fed_scopes


def read(ctx):
    return fed_scopes.share(
        ctx, lambda op: fed_scopes.innermost(op) is not None) or 0.0
