"""ModelBundle — the framework's model operator contract.

The reference abstracts models behind the ``ModelTrainer`` ABC
(``fedml_core/trainer/model_trainer.py:4-32``) whose docstring promises
framework-agnosticism.  Here the contract is functional: a flax module
plus pure ``init`` / ``apply_train`` / ``apply_eval`` closures over an
explicit ``variables`` pytree (``{'params': ..., 'batch_stats': ...}``).
Mutable BatchNorm statistics — the awkward hidden state of the torch
version (naively averaged by FedAvg, skipped by robust vectorization,
``robust_aggregation.py:28-29``) — are explicit leaves of the same tree,
so aggregation policy over them is a visible choice, not an accident.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

PyTree = Any

# Reserved collection for scalar counters a model hands out of its train-mode
# forward (tokens an expert layer kept, the fullest expert's rows): sown by
# the module, returned by ``apply_train`` beside the variables, taken out by
# ``make_local_update`` before the carry and summed into its metrics.  Never
# part of ``variables``: ``init`` does not create it, so aggregation, codecs
# and checkpoints never see it.
COUNTERS = "counters"


@dataclasses.dataclass
class ModelBundle:
    """A flax module + its input spec, wrapped as pure functions."""

    module: nn.Module
    input_shape: Sequence[int]  # one example's shape, no batch dim
    input_dtype: Any = jnp.float32
    needs_dropout_rng: bool = False
    has_counters: bool = False  # the module sows into ``COUNTERS`` in train mode

    def init(self, rng: jax.Array) -> PyTree:
        dummy = jnp.zeros((1, *self.input_shape), self.input_dtype)
        rngs = {"params": rng}
        if self.needs_dropout_rng:
            rngs["dropout"] = jax.random.fold_in(rng, 1)
        return self.module.init(rngs, dummy, train=False)

    def apply_train(
        self, variables: PyTree, x: jax.Array, rng: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, PyTree]:
        """Forward in train mode; returns (logits, updated variables)."""
        rngs = {"dropout": rng} if (self.needs_dropout_rng and rng is not None) else None
        mutable = ["batch_stats"] if "batch_stats" in variables else []
        if self.has_counters:
            mutable.append(COUNTERS)
        if mutable:
            logits, mutated = self.module.apply(
                variables, x, train=True, mutable=mutable, rngs=rngs
            )
            return logits, {**variables, **mutated}
        logits = self.module.apply(variables, x, train=True, rngs=rngs)
        return logits, variables

    def apply_eval(self, variables: PyTree, x: jax.Array) -> jax.Array:
        return self.module.apply(variables, x, train=False)
