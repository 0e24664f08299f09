"""Share of device busy time in the vocabulary head and the loss: every op
of ``fed.loss`` (log-softmax over the vocabulary, forward and backward) and
the model's ops under ``wte.attend`` (the tied head's forward matmul and its
two backward ones).  A cut across the forward/backward partition; the module
name is the transformer family's."""

from benchmark import fed_scopes

SCOPES = ("fed.loss", "fed.model")


def read(ctx):
    def keep(op):
        scope = fed_scopes.innermost(op)
        return scope == "fed.loss" or (
            scope == "fed.model" and "wte.attend" in fed_scopes.tf_op(op))

    return fed_scopes.share(ctx, keep)
