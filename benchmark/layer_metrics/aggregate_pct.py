"""Share of device busy time in the aggregation: the weights, the weighted
sum over the client stack, its ``psum`` across chips (which is in
``collective_pct`` too: the two cuts cross), the guarded divide, the server
update and the metric sums."""

from benchmark import fed_scopes

SCOPES = ("fed.aggregate", "fed.server_update", "fed.metrics")


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES)
