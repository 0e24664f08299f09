"""Share of device busy time in the attention over chosen keys: the ops whose
innermost ``model.*`` scope is ``model.attn_sparse`` (``fedml_tpu/obs/
scopes.py``: the attention function of a layer whose keys an indexer picks,
the flash kernels with a choice or the lax scan, and the relayouts of the mask
around them), forward and backward.  A cut across the forward/backward
partition, inside ``fed.model``.  Nothing where no op carries the scope (a
program without the layer)."""

from benchmark import model_scopes

SCOPE = "model.attn_sparse"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
