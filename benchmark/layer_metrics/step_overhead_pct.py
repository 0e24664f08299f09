"""Share of device busy time a local update spends around the model, the
loss and the optimizer: casts of masters and inputs to the compute dtype
(forward or backward), the epoch's shuffle, batch slicing by the step scan,
key folding, metric sums."""

from benchmark import fed_scopes

SCOPES = ("fed.local_update", "fed.shuffle", "fed.step", "fed.cast")


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES)
