"""Device time by part of the model step, from the ``model.*`` scopes the
program puts in its ops' names inside ``fed.model``
(``fedml_tpu/obs/scopes.py`` ``MODEL_SCOPES``; this file imports neither JAX
nor the program, so the names its callers pass are literals that
``tests/test_model_scopes.py`` holds against the program's).

An op belongs to its innermost part: the last ``model.<name>`` of its
``tf_op``.  No scope of the program goes around a whole block or mixer, so
the older names keep every op they had.  As for the stages
(``fed_scopes.py``): attribution follows the fusion, and an executable the
compile cache kept from before a scope has no op under it, and then a reader
says nothing rather than zero."""

from __future__ import annotations

import functools
import re

from benchmark import fed_scopes

SCOPE = re.compile(r"model\.[a-z_]+")


@functools.lru_cache(maxsize=None)  # a trace repeats a few thousand names
def _innermost(name: str):
    found = SCOPE.findall(name)
    return found[-1] if found else None


def innermost(op):
    """The op's innermost ``model.*`` scope, or None."""
    return _innermost(fed_scopes.tf_op(op))


def seconds(ctx, scope, klass=None, backward=None):
    """Mean seconds a device of the ops whose innermost ``model.*`` scope is
    ``scope``; ``klass`` keeps one op class, ``backward`` True or False one
    direction.  None when no op of the trace carries the scope."""
    s = ctx.summary
    if not any(innermost(op) == scope for d in s.devices for op in d.ops):
        return None
    return s.seconds_where(lambda op: innermost(op) == scope and (
        klass is None or op.klass == klass) and (
        backward is None or fed_scopes.is_backward(op) == backward))


def share(ctx, scope):
    """Share (%) of device busy time under ``scope``, forward and backward;
    None when no op of the trace carries it."""
    under = seconds(ctx, scope)
    return None if under is None else 100.0 * under / ctx.summary.busy_s
