"""Of the device seconds of ops whose stage is ``fed.model``, the share
that carries any ``model.*`` scope.  The rest is what the model computes
outside every named part: the residual adds, a cast or a layout copy between
parts.  Nothing on a trace without a ``model.*`` scope (a model that names
no part, or an executable the compile cache kept from before)."""

from benchmark import fed_scopes, model_scopes

STAGE = "fed.model"


def in_model(op) -> bool:
    return fed_scopes.innermost(op) == STAGE


def read(ctx):
    s = ctx.summary
    named = s.seconds_where(
        lambda op: in_model(op) and model_scopes.innermost(op) is not None)
    if not named:
        return None
    return 100.0 * named / s.seconds_where(in_model)
