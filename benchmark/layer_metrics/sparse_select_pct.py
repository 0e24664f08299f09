"""Share of device busy time in choosing the keys: the ops whose innermost
``model.*`` scope is ``model.attn_indexer`` (the three index projections, the
index key's LayerNorm, the rotation of ``qI`` and ``kI``) or
``model.attn_select`` (index scores, the choice, the tile table).  Forward
only by construction: the choice is discrete and nothing differentiates it.
Nothing where no op carries either scope."""

from benchmark import model_scopes

SCOPES = ("model.attn_indexer", "model.attn_select")


def read(ctx):
    shares = [model_scopes.share(ctx, s) for s in SCOPES]
    if all(s is None for s in shares):
        return None
    return sum(s or 0.0 for s in shares)
