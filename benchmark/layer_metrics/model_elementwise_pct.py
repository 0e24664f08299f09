"""Share of device busy time in the model's ops that are in no op class of
``trace_rules.json``: LayerNorm, GELU, residual adds, the embedding's gather
and scatter.  A cut across the forward/backward partition."""

from benchmark import fed_scopes

SCOPES = ("fed.model",)


def read(ctx):
    return fed_scopes.share(
        ctx, lambda op: fed_scopes.innermost(op) in SCOPES
        and op.klass == "other")
