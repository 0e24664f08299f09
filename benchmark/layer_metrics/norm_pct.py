"""Share of device busy time in the norms: the ops whose innermost
``model.*`` scope is ``model.norm`` (``fedml_tpu/obs/scopes.py``: the norms
before and, where a block has them, after the mixer and the MLP, the q/k head
norms and the final norm, at their call sites), forward and backward.  A cut
across the forward/backward partition, inside ``fed.model``.  It reads only
what keeps the name: a norm that XLA fuses into a neighbouring product is
that product's.  Nothing where no op carries the scope."""

from benchmark import model_scopes

SCOPE = "model.norm"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
