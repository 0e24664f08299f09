"""Share of device busy time in the client optimizer: ``optimizer.update``,
``apply_updates`` and the blend that makes an all-padding batch a no-op."""

from benchmark import fed_scopes

SCOPES = ("fed.optimizer",)


def read(ctx):
    return fed_scopes.stage_share(ctx, SCOPES)
