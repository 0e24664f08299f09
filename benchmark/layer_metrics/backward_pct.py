"""Share of device busy time in the backward pass: the model's apply and
the loss under ``transpose(...)``.  Recomputation lands here, so a remat
change raises it knowingly."""

from benchmark import fed_scopes

SCOPES = ("fed.model", "fed.loss")


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES, backward=True)
