"""Share of device busy time in the forward pass: the model's apply and the
loss, outside ``transpose(...)``."""

from benchmark import fed_scopes

SCOPES = ("fed.model", "fed.loss")


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES, backward=False)
