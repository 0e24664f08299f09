"""Share of device busy time the round kernel spends around the clients'
training and before the aggregation.  In the cells as they stand that is the
``[K, ...]`` stack of client models: the writes into it, the copies of the
client loop's carry and the slicing of the cohort; key folding and the round
scan's carry are in it and are small.  Three scopes, because XLA hangs an op
it makes itself on the enclosing call: the client map's body, the round's or
the round scan's."""

from benchmark import fed_scopes

SCOPES = ("fed.clients", "fed.round", "fed.rounds")


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES)
