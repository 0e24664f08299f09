"""Share of device busy time in the vocabulary head: the ops whose innermost
``model.*`` scope is ``model.head`` (``tok.attend`` of ``TransformerLM``,
``lm_head`` of ``DecoderLM``: the forward product and the two backward ones,
with what XLA fuses into them: the loss's backward, the final norm's).  In
every model family, where ``head_loss_pct`` reads one family's module name.
A cut across the forward/backward partition, inside ``fed.model``."""

from benchmark import model_scopes

SCOPE = "model.head"


def read(ctx):
    return model_scopes.share(ctx, SCOPE)
