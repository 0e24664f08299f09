"""Share of device busy time in the latent expert layer's projections: the
ops whose innermost ``model.*`` scope is ``model.moe_latent``
(``fedml_tpu/obs/scopes.py``: the routed experts' down-projection of the
normed input into their latent width and the up-projection of their weighted
sum), forward and backward.  A cut across the forward/backward partition,
inside ``fed.model``.  Nothing where no op carries the scope (a program
whose experts work at the hidden width)."""

from benchmark import model_scopes

SCOPE = "model.moe_latent"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
