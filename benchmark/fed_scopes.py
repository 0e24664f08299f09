"""Device time by stage of the federated round, from the ``fed.*`` scopes
the program puts in its ops' names (``fedml_tpu/obs/scopes.py``; this file
imports neither JAX nor the program, so the names it reads are literals that
``tests/test_fed_scopes.py`` holds against the program's).

A scope is a path segment of an op's ``tf_op``; inside ``value_and_grad`` JAX
wraps it, ``jvp(fed.model)`` forward and ``transpose(jvp(fed.model))``
backward.  An op belongs to its innermost scope: the last ``fed.<name>`` in
the string.  XLA names a fusion after one of the ops it fused, so a cast
fused into a matmul counts as the matmul's stage: attribution follows the
fusion.  An executable the compile cache kept from before the scopes has
none, and then a stage reader says nothing rather than zero."""

from __future__ import annotations

import functools
import re

SCOPE = re.compile(r"fed\.[a-z_]+")


def tf_op(op) -> str:
    return str(op.stats.get("tf_op", ""))


@functools.lru_cache(maxsize=None)  # a trace repeats a few thousand names
def _innermost(name: str):
    found = SCOPE.findall(name)
    return found[-1] if found else None


def innermost(op):
    """The op's innermost ``fed.*`` scope, or None."""
    return _innermost(tf_op(op))


def is_backward(op) -> bool:
    return "transpose(" in tf_op(op)


def share(ctx, keep):
    """Share (%) of device busy time in the ops ``keep(op)`` accepts; None
    when no op of the trace carries a scope."""
    s = ctx.summary
    if not any(innermost(op) for d in s.devices for op in d.ops):
        return None
    return 100.0 * s.seconds_where(keep) / s.busy_s


def stage_share(ctx, scopes, backward=None):
    """``share`` of the ops whose innermost scope is one of ``scopes``;
    ``backward`` True or False keeps only that direction."""
    return share(ctx, lambda op: innermost(op) in scopes and (
        backward is None or is_backward(op) == backward))
