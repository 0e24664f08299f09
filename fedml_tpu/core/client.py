"""The client-side local training operator.

TPU-native replacement for the reference's ``MyModelTrainer.train``
Python epoch/batch loop (``fedml_api/distributed/fedavg/MyModelTrainer.py:26-71``
and ``standalone/fedavg/my_model_trainer_classification.py:17-54``):
a jit-compiled ``lax.scan`` over epochs × fixed-shape batches, vmappable
over a packed client axis and shard_mappable over a device mesh.

Matches the reference's semantics:
- the client optimizer is constructed fresh every round (``MyModelTrainer.py:33-41``);
- per-epoch reshuffling of the local dataset (torch DataLoader shuffle=True);
- optional proximal term for FedProx (``fedprox/MyModelTrainer.py:41-60``),
  computed over parameters only — the reference's buffer/parameter index
  misalignment (SURVEY.md §7 "known defects") is not replicated;
- optional global-norm gradient clipping
  (``my_model_trainer_classification.py:44``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.core import tree as treelib
from fedml_tpu.core.losses import LossFn, masked_softmax_ce
from fedml_tpu.models.base import COUNTERS, ModelBundle
from fedml_tpu.obs import scopes

PyTree = Any


def _scale_by_amsgrad_torch(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> optax.GradientTransformation:
    """torch.optim.Adam(amsgrad=True) semantics exactly: the running max
    is over the RAW second moment, and bias correction divides the max
    (optax.amsgrad maxes the bias-corrected nu instead, which diverges
    from torch over the first steps — verified numerically)."""

    def init(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"count": jnp.zeros((), jnp.int32), "mu": zeros,
                "nu": zeros, "nu_max": zeros}

    def update(updates, state, params=None):
        del params
        t = state["count"] + 1
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, state["mu"], updates)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], updates)
        nu_max = jax.tree_util.tree_map(jnp.maximum, state["nu_max"], nu)
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)
        out = jax.tree_util.tree_map(
            lambda m, v: (m / c1) / (jnp.sqrt(v / c2) + eps), mu, nu_max)
        return out, {"count": t, "mu": mu, "nu": nu, "nu_max": nu_max}

    return optax.GradientTransformation(init, update)


def make_client_optimizer(
    name: str = "sgd",
    lr: float = 0.03,
    *,
    momentum: float = 0.0,
    weight_decay: Optional[float] = None,
    grad_clip: Optional[float] = None,
) -> optax.GradientTransformation:
    """The reference's client optimizers: SGD (+momentum/wd) or amsgrad Adam
    (``MyModelTrainer.py:33-41``).

    ``weight_decay=None`` means "optimizer default" (0 for sgd, the
    reference's 1e-4 for adam); an explicit 0.0 is honored as zero so
    wd=0 runs are reproducible.
    """
    chain = []
    if grad_clip is not None:
        chain.append(optax.clip_by_global_norm(grad_clip))
    if name == "sgd":
        if weight_decay:
            chain.append(optax.add_decayed_weights(weight_decay))
        chain.append(optax.sgd(lr, momentum=momentum if momentum else None))
    elif name == "adam":
        # reference default: torch.optim.Adam(lr, weight_decay=0.0001,
        # amsgrad=True) (MyModelTrainer.py:38-40).  torch's weight_decay
        # is COUPLED L2 (wd*p added to the gradient before the adam
        # update), so add_decayed_weights goes BEFORE the scaling — not
        # decoupled adamw
        wd = 1e-4 if weight_decay is None else weight_decay
        if wd:
            chain.append(optax.add_decayed_weights(wd))
        chain.append(_scale_by_amsgrad_torch())
        # scale_by_learning_rate = scale(-lr), and also accepts an optax
        # schedule (count -> lr) like the sgd branch does
        chain.append(optax.scale_by_learning_rate(lr))
    else:
        raise ValueError(f"unknown client optimizer: {name}")
    return optax.chain(*chain)


@dataclasses.dataclass
class LocalUpdateFn:
    """Callable local update plus metadata the algorithms need."""

    fn: Callable  # (variables, x, y, mask, rng) -> (variables, metrics)
    epochs: int

    def __call__(self, variables, x, y, mask, rng):
        return self.fn(variables, x, y, mask, rng)


def _split_counters(new_vars):
    """(the variables a model came back with, less its ``COUNTERS``
    collection; that collection as an entry for the step's aux), so the
    counters never enter the scan carry.  A model without one (every model
    but the expert decoder) passes through untouched."""
    if COUNTERS not in new_vars:
        return new_vars, {}
    rest = {k: v for k, v in new_vars.items() if k != COUNTERS}
    return rest, {COUNTERS: dict(new_vars[COUNTERS])}


def make_local_update(
    bundle: ModelBundle,
    optimizer: optax.GradientTransformation,
    epochs: int,
    loss_fn: LossFn = masked_softmax_ce,
    *,
    prox_mu: float = 0.0,
    shuffle: bool = True,
    augment_fn: Optional[Callable] = None,
    compute_dtype: Optional[Any] = None,
    unroll: int = 1,
) -> LocalUpdateFn:
    """Build the pure local-update function for one client.

    Args shapes (one client): x [steps, B, ...], y [steps, B], mask [steps, B].
    Returns (new_variables, metrics) where metrics carries summed
    loss/correct/count over the final epoch — mirroring what the
    reference logs per client (``MyModelTrainer.py:55-66``).

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) enables mixed precision:
    the forward/backward pass runs with params and inputs cast to that
    dtype so matmuls/convs hit the MXU at full rate, while the master
    params, optimizer state, gradients, and loss stay float32 (losses
    upcast logits internally).  Mutable state (BatchNorm stats) is cast
    back to its master dtype each step so the scan carry stays stable.
    """

    def loss_and_logits(params, other_vars, global_params, x, y, m, rng):
        variables = {**other_vars, "params": params}
        if compute_dtype is not None:
            with jax.named_scope(scopes.CAST):
                cvars = treelib.tree_cast_floats(variables, compute_dtype)
                cx = (
                    x.astype(compute_dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else x
                )
            with jax.named_scope(scopes.MODEL):
                logits, new_vars = bundle.apply_train(cvars, cx, rng)
            new_vars, counters = _split_counters(new_vars)
            with jax.named_scope(scopes.CAST):
                new_vars = treelib.tree_cast_like(new_vars, variables)
        else:
            with jax.named_scope(scopes.MODEL):
                logits, new_vars = bundle.apply_train(variables, x, rng)
            new_vars, counters = _split_counters(new_vars)
        with jax.named_scope(scopes.LOSS):
            loss, aux = loss_fn(logits, y, m)
            aux = {**aux, **counters}
            if prox_mu:
                sq = treelib.tree_sq_norm(treelib.tree_sub(params, global_params))
                loss = loss + 0.5 * prox_mu * sq
        return loss, (new_vars, aux)

    grad_fn = jax.value_and_grad(loss_and_logits, has_aux=True)

    def local_update(variables, x, y, mask, rng):
        steps, bsz = x.shape[0], x.shape[1]
        n = steps * bsz
        global_params = variables["params"]
        opt_state = optimizer.init(variables["params"])

        def epoch_body(carry, ep):
            variables, opt_state = carry
            ek = jax.random.fold_in(rng, ep)
            with jax.named_scope(scopes.SHUFFLE):
                if shuffle:
                    perm = jax.random.permutation(jax.random.fold_in(ek, 0), n)
                    xs = x.reshape(n, *x.shape[2:])[perm].reshape(x.shape)
                    ys = y.reshape(n, *y.shape[2:])[perm].reshape(y.shape)
                    ms = mask.reshape(n)[perm].reshape(mask.shape)
                else:
                    xs, ys, ms = x, y, mask
                if augment_fn is not None:
                    # fresh augmentation for every sample once per EPOCH —
                    # exactly the reference's torchvision semantics (each
                    # sample is transformed once per pass) — applied to the
                    # whole epoch tensor in ONE call.  Per-STEP augmentation
                    # is semantically identical but ~15x slower end-to-end:
                    # the augment's ~6 threefry/elementwise kernels cost
                    # ~1.5 ms per scan step on v5e (latency-, not
                    # bandwidth-bound), which at north-star scale (15,600
                    # steps/round) added ~25 s/round and pushed the round
                    # over the ~70 s device-execution deadline (measured;
                    # one whole-epoch call costs ~0.1 ms for 5,000 images)
                    flat = augment_fn(
                        jax.random.fold_in(ek, n + 1),
                        xs.reshape(n, *x.shape[2:]),
                    )
                    xs = flat.reshape(x.shape)

            def step_body(carry, batch):
                with jax.named_scope(scopes.STEP):
                    variables, opt_state = carry
                    bx, by, bm, bi = batch
                    sk = jax.random.fold_in(ek, bi + 1)
                    others = {k: v for k, v in variables.items() if k != "params"}
                    (loss, (new_vars, aux)), grads = grad_fn(
                        variables["params"], others, global_params, bx, by, bm, sk
                    )
                    with jax.named_scope(scopes.OPTIMIZER):
                        updates, new_opt = optimizer.update(
                            grads, opt_state, variables["params"]
                        )
                        params = optax.apply_updates(variables["params"], updates)
                        # batches that are entirely padding must be true no-ops
                        has_real = (bm.sum() > 0).astype(jnp.float32)
                        params = jax.tree_util.tree_map(
                            lambda new, old: has_real * new + (1 - has_real) * old,
                            params,
                            variables["params"],
                        )
                    new_vars = {**new_vars, "params": params}
                    aux = {**aux, "step": has_real}
                return (new_vars, new_opt), aux

            # unroll>1 trades compiled-code size for fewer while-loop
            # iterations: the TPU loop bookkeeping is ~0.3ms/iteration,
            # a measurable share of a ~4ms step (profiled on v5e)
            (variables, opt_state), auxs = jax.lax.scan(
                step_body,
                (variables, opt_state),
                (xs, ys, ms, jnp.arange(steps)),
                unroll=unroll,
            )
            return (variables, opt_state), auxs

        if epochs == 1:
            # elide the outer while loop entirely: the TPU scalar-core
            # bookkeeping for a length-1 scan is pure overhead (the
            # PROFILE.md `while` share), and E=1 is the reference's
            # default benchmark regime.  fold_in(rng, 0) keeps the RNG
            # stream identical to the scan path.
            (variables, _), auxs0 = epoch_body((variables, opt_state), 0)
            auxs = jax.tree_util.tree_map(lambda a: a[None], auxs0)
        else:
            (variables, _), auxs = jax.lax.scan(
                epoch_body, (variables, opt_state), jnp.arange(epochs)
            )
        metrics = {
            "loss_sum": auxs["loss_sum"][-1].sum(),
            "correct": auxs["correct"][-1].sum(),
            "count": auxs["count"][-1].sum(),
            # exact optimizer steps executed across ALL epochs (pad-only
            # batches are no-ops and excluded) — FedNova's tau_i
            "steps": auxs["step"].sum(),
            # a model's own scalar counters, summed like the loss
            **{k: v[-1].sum() for k, v in auxs.get(COUNTERS, {}).items()},
        }
        return variables, metrics

    return LocalUpdateFn(fn=local_update, epochs=epochs)


def make_evaluator(bundle: ModelBundle, loss_fn: LossFn = masked_softmax_ce):
    """Jit-able eval over a padded batch pack [steps, B, ...] → summed metrics.

    Evaluation stays float32 even when training uses a low-precision
    compute_dtype: metric fidelity is worth the one fp32 forward."""

    def evaluate(variables, x, y, mask):
        def body(carry, batch):
            bx, by, bm = batch
            logits = bundle.apply_eval(variables, bx)
            _, aux = loss_fn(logits, by, bm)
            return carry, aux

        _, auxs = jax.lax.scan(body, (), (x, y, mask))
        return {k: v.sum() for k, v in auxs.items()}

    return jax.jit(evaluate)


def eval_summary(res) -> dict:
    """Summed evaluator metrics → the test_{acc,loss,count} record every
    driver reports (shared so the simulation and DP×TP paths can't
    drift apart)."""
    count = float(res["count"])
    out = {
        "test_acc": float(res["correct"]) / max(count, 1.0),
        "test_loss": float(res["loss_sum"]) / max(count, 1.0),
        "test_count": count,
    }
    # multi-label tasks (losses.masked_multilabel_bce) also report the
    # reference's precision/recall (my_model_trainer_tag_prediction.py:88-93)
    if "precision_sum" in res:
        out["test_precision"] = float(res["precision_sum"]) / max(count, 1.0)
        out["test_recall"] = float(res["recall_sum"]) / max(count, 1.0)
    return out
