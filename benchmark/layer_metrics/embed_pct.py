"""Share of device busy time in the token embedding: the ops whose innermost
``model.*`` scope is ``model.embed`` (the lookup, and in ``TransformerLM`` the
positions' and their add); its backward is the scatter-add into the table.
A cut across the forward/backward partition, inside ``fed.model``."""

from benchmark import model_scopes

SCOPE = "model.embed"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
