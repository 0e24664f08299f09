"""Masked loss / metric functions.

Padding-by-wrapping (core.types.pack_clients) means every batch may
contain duplicate "pad" samples; all losses here take a ``mask`` and
normalize by the real-sample count so padded slots contribute exactly
zero gradient and zero metric weight.  This replaces the reference's
reliance on torch DataLoader ragged last batches
(``MyModelTrainer.py:44-52``).
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# A LossFn maps (logits, targets, mask) -> (mean_loss, aux_metrics)
LossFn = Callable[[jax.Array, jax.Array, jax.Array], Tuple[jax.Array, dict]]


def softmax_ce_logits(logits: jax.Array, y: jax.Array) -> jax.Array:
    """Per-example cross-entropy with integer targets (no mask) — the
    plain ``nn.CrossEntropyLoss`` used where batches are full-shape
    (SplitNN server, ``split_nn/server.py:21``)."""
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), y.astype(jnp.int32)
    )


@jax.custom_vjp
def _softmax_nll(logits: jax.Array, y: jax.Array) -> jax.Array:
    """``-log_softmax(logits.astype(f32))[..., y]`` for integer ``y``:
    any float dtype, any leading shape, float32 out."""
    return _softmax_nll_fwd(logits, y)[0]


def _softmax_nll_fwd(logits, y):
    x = logits.astype(jnp.float32)
    shift = jax.lax.stop_gradient(x.max(axis=-1))
    sum_exp = jnp.exp(x - shift[..., None]).sum(axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    nll = jnp.log(sum_exp) - (picked.astype(jnp.float32) - shift)
    return nll, (logits, y, shift, sum_exp)


def _softmax_nll_bwd(res, g):
    logits, y, shift, sum_exp = res
    e = jnp.exp(logits.astype(jnp.float32) - shift[..., None])
    onehot = jax.nn.one_hot(y, logits.shape[-1], dtype=jnp.float32)
    dlogits = e * (g / sum_exp)[..., None] - onehot * g[..., None]
    return dlogits.astype(logits.dtype), np.zeros(y.shape, jax.dtypes.float0)


_softmax_nll.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


def masked_softmax_ce(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Cross-entropy with integer targets; mean over mask.

    Handles both [B, C] classification and [B, T, C] sequence shapes
    (Shakespeare/StackOverflow next-token tasks); for sequences the mask
    is broadcast over time unless given per-token.

    The per-token loss is ``lse - logit[label]`` with its own backward
    (``_softmax_nll``).  Saved for the backward: the logits in the dtype
    they came in, the labels, and the log-sum-exp of each row in its
    two float32 parts, the max and the sum of ``exp(logit - max)``; the
    backward is ``(softmax - onehot) * g`` rounded to the logits' dtype.
    Max, exp, sum, log and the subtractions run in float32 in the order
    ``log_softmax(logits.astype(f32))`` and its transpose run them, so
    loss and gradient are that form's to the bit.  That form, with
    ``take_along_axis`` after it, made XLA write the float32
    log-softmax of the whole vocabulary to read one entry a token from
    it: 1.5 GiB a step at 8192 x 50257 (PERF.md, PR 28).  A
    ``custom_vjp`` takes no forward mode (``jvp``, ``jacfwd``); reverse
    over reverse (FedNAS) works.
    """
    nll = _softmax_nll(logits, y.astype(jnp.int32))
    if nll.ndim > mask.ndim:
        mask = jnp.broadcast_to(mask[..., None], nll.shape)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    pred = jnp.argmax(logits, axis=-1)
    correct = ((pred == y) * mask).sum()
    return loss, {"loss_sum": (nll * mask).sum(), "correct": correct, "count": mask.sum()}


def _bce_elements(logits: jax.Array, yf: jax.Array) -> jax.Array:
    """Numerically stable per-element BCE-with-logits."""
    return (
        jnp.maximum(logits, 0) - logits * yf
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def masked_bce_logits(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Binary cross-entropy on logits (VFL / lending-club binary tasks)."""
    logits = logits.astype(jnp.float32).reshape(y.shape)
    yf = y.astype(jnp.float32)
    per = _bce_elements(logits, yf)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per * mask).sum() / denom
    pred = (logits > 0).astype(yf.dtype)
    correct = ((pred == yf) * mask).sum()
    return loss, {"loss_sum": (per * mask).sum(), "correct": correct, "count": mask.sum()}


def masked_multilabel_bce(logits: jax.Array, y: jax.Array, mask: jax.Array):
    """Multi-label tag prediction: per-sample BCE summed over the label
    axis, plus the reference's exact-match / precision / recall metrics
    (``standalone/fedavg/my_model_trainer_tag_prediction.py:24,54-96``:
    ``nn.BCELoss(reduction='sum')`` on sigmoid outputs; ``predicted =
    (pred > .5)``; "correct" counts samples whose ENTIRE tag vector
    matches).

    Shapes: logits [B, C] (or [..., C]), y multi-hot [..., C] float,
    mask [...] per-sample.  Loss = masked MEAN over samples of the
    per-sample label-summed BCE.

    Deliberate deviation from the reference TRAINING objective: the
    reference optimizes the raw ``reduction='sum'`` value, so its
    gradient scales with the per-client batch/sample count and its
    published stackoverflow_lr lr is tuned to that scale.  Here the loss
    is the per-sample mean (count-invariant gradients — the convention
    every other loss in this module follows, and the one that keeps one
    lr meaningful across heterogeneous client sizes).  Reference lr
    values for this task must be rescaled by the per-client batch size
    (lr_here ≈ lr_ref × batch_size); the sum is still reported as
    ``loss_sum`` so METRICS match the reference exactly.  See
    PARITY.md §losses.
    """
    logits = logits.astype(jnp.float32).reshape(y.shape)
    yf = y.astype(jnp.float32)
    per = _bce_elements(logits, yf).sum(axis=-1)  # BCELoss(sum) per sample
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per * mask).sum() / denom
    pred = (logits > 0.0).astype(jnp.float32)  # sigmoid(z) > .5  ⇔  z > 0
    exact = jnp.all(pred == yf, axis=-1).astype(jnp.float32)
    tp = (yf * pred).sum(axis=-1)
    precision = tp / (pred.sum(axis=-1) + 1e-13)
    recall = tp / (yf.sum(axis=-1) + 1e-13)
    return loss, {
        "loss_sum": (per * mask).sum(),
        "correct": (exact * mask).sum(),
        "count": mask.sum(),
        "precision_sum": (precision * mask).sum(),
        "recall_sum": (recall * mask).sum(),
    }


def masked_kd_kl(
    student_logits: jax.Array,
    teacher_logits: jax.Array,
    mask: jax.Array,
    temperature: float = 3.0,
) -> jax.Array:
    """Knowledge-distillation KL with temperature, mean over mask.

    Matches the reference's ``KL_Loss`` (``fedgkt/utils.py``):
    ``T² · KL(softmax(teacher/T) ‖ softmax(student/T))``.
    """
    t = temperature
    logp_s = jax.nn.log_softmax(student_logits.astype(jnp.float32) / t, axis=-1)
    p_t = jax.nn.softmax(teacher_logits.astype(jnp.float32) / t, axis=-1)
    logp_t = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / t, axis=-1)
    per = (p_t * (logp_t - logp_s)).sum(axis=-1) * (t * t)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (per * mask).sum() / denom


def masked_mse(preds: jax.Array, y: jax.Array, mask: jax.Array):
    preds = preds.astype(jnp.float32).reshape(y.shape)
    per = jnp.square(preds - y.astype(jnp.float32))
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per * mask).sum() / denom
    return loss, {"loss_sum": (per * mask).sum(), "correct": jnp.zeros(()), "count": mask.sum()}
