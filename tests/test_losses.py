"""masked_softmax_ce against the plain formula it replaced.

The loss keeps the logits and each row's max and sum of exp for its
backward (core/losses.py `_softmax_nll`); the plain form written out here,
`log_softmax(x.astype(f32))` then `take_along_axis`, is the reference:
values, every aux entry, the gradient and its dtype, the shapes of use
in the client loop (vmap, scan), FedNAS's gradient of a gradient, and
what the backward keeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.losses import masked_softmax_ce


def plain_softmax_ce(logits, y, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), axis=-1)[..., 0]
    if nll.ndim > mask.ndim:
        mask = jnp.broadcast_to(mask[..., None], nll.shape)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    correct = ((jnp.argmax(logits, axis=-1) == y) * mask).sum()
    return loss, {"loss_sum": (nll * mask).sum(), "correct": correct, "count": mask.sum()}


def loss_only(loss_fn):
    return lambda *args: loss_fn(*args)[0]


def make_case(shape, dtype, mask_kind, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=shape) * scale, dtype)
    y = jnp.asarray(rng.integers(0, shape[-1], size=shape[:-1]), jnp.int32)
    if mask_kind == "sample":
        mask = rng.random(shape[:1]) < 0.7
    elif mask_kind == "token":
        mask = rng.random(shape[:-1]) < 0.7
    else:
        mask = np.zeros(shape[:1], bool)
    return logits, y, jnp.asarray(mask, jnp.float32)


CASES = {
    "BC_f32": ((6, 10), jnp.float32, "sample"),
    "BC_bf16": ((6, 10), jnp.bfloat16, "sample"),
    "BTC_bf16_sample_mask": ((4, 7, 37), jnp.bfloat16, "sample"),
    "BTC_bf16_token_mask": ((4, 7, 37), jnp.bfloat16, "token"),
}


@pytest.mark.parametrize("shape,dtype,mask_kind", CASES.values(), ids=CASES.keys())
def test_matches_plain_formula(shape, dtype, mask_kind):
    logits, y, mask = make_case(shape, dtype, mask_kind)
    assert float(mask.sum()) > 0
    (loss, aux), grad = jax.value_and_grad(masked_softmax_ce, has_aux=True)(logits, y, mask)
    (ref_loss, ref_aux), ref_grad = jax.value_and_grad(plain_softmax_ce, has_aux=True)(logits, y, mask)
    np.testing.assert_allclose(loss, ref_loss, rtol=0, atol=1e-6)
    assert aux.keys() == ref_aux.keys()
    for k in aux:
        np.testing.assert_allclose(aux[k], ref_aux[k], rtol=1e-6, atol=1e-6, err_msg=k)
    assert grad.dtype == logits.dtype and grad.shape == logits.shape
    if dtype == jnp.float32:
        np.testing.assert_allclose(grad, ref_grad, rtol=0, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(grad, np.float32), np.asarray(ref_grad, np.float32))


def test_all_padding_mask_gives_zero_gradient_and_finite_values():
    logits, y, mask = make_case((4, 7, 37), jnp.bfloat16, "none")
    (loss, aux), grad = jax.value_and_grad(masked_softmax_ce, has_aux=True)(logits, y, mask)
    assert float(loss) == 0.0 and float(aux["count"]) == 0.0
    assert all(np.isfinite(np.asarray(v)) for v in aux.values())
    assert not np.asarray(grad, np.float32).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_logits_of_magnitude_1e4_stay_finite(dtype):
    logits, y, mask = make_case((6, 33), dtype, "sample", scale=1e4)
    (loss, aux), grad = jax.value_and_grad(masked_softmax_ce, has_aux=True)(logits, y, mask)
    ref_loss, _ = plain_softmax_ce(logits, y, mask)
    assert np.isfinite(float(loss)) and np.isfinite(np.asarray(grad, np.float32)).all()
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    assert all(np.isfinite(np.asarray(v)) for v in aux.values())


def test_under_vmap_and_inside_scan():
    """The client loop's two shapes of use: `vmap` over clients, and a
    `lax.scan` over steps with the gradient taken inside the body."""
    per_client = [make_case((4, 7, 37), jnp.bfloat16, "token", seed=s) for s in range(3)]
    logits, y, mask = (jnp.stack(a) for a in zip(*per_client))
    ref = [jax.value_and_grad(loss_only(plain_softmax_ce))(*c) for c in per_client]
    ref_loss = np.stack([np.asarray(l) for l, _ in ref])
    ref_grad = np.stack([np.asarray(g, np.float32) for _, g in ref])

    loss_v, aux_v = jax.vmap(masked_softmax_ce)(logits, y, mask)
    np.testing.assert_allclose(loss_v, ref_loss, rtol=0, atol=1e-6)
    assert aux_v["count"].shape == (3,)
    grad_v = jax.vmap(jax.grad(loss_only(masked_softmax_ce)))(logits, y, mask)
    assert grad_v.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(grad_v, np.float32), ref_grad)

    def body(total, xs):
        loss, grad = jax.value_and_grad(loss_only(masked_softmax_ce))(*xs)
        return total + loss, grad

    total, grad_s = jax.lax.scan(body, jnp.zeros(()), (logits, y, mask))
    np.testing.assert_allclose(total, ref_loss.sum(), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(grad_s, np.float32), ref_grad)


def test_gradient_of_a_gradient_matches_plain_form():
    """FedNAS's architect step differentiates a function of the loss's
    gradient (algorithms/fednas.py): reverse over reverse."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 9)), jnp.float32)
    alpha = jnp.asarray(rng.normal(size=(9,)), jnp.float32)
    _, y, mask = make_case((6, 9), jnp.float32, "sample", seed=4)

    def second_order(loss_fn):
        def train_loss(w_, alpha_):
            return loss_fn((x @ w_) * jnp.tanh(alpha_), y, mask)[0]

        def after_one_step(alpha_):
            w_new = w - 0.1 * jax.grad(train_loss)(w, alpha_)
            return train_loss(w_new, alpha_)

        return jax.grad(after_one_step)(alpha)

    got, want = second_order(masked_softmax_ce), second_order(plain_softmax_ce)
    assert np.abs(np.asarray(want)).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_backward_keeps_no_float32_table_of_the_vocabulary():
    """The memory claim (PERF.md, PR 28): the residuals are the logits as
    they came, the labels and two float32 a row, never a float32 array
    whose last dimension is the vocabulary."""
    vocab = 64
    logits, y, mask = make_case((2, 16, vocab), jnp.bfloat16, "token")
    _, vjp_fn = jax.vjp(lambda l: masked_softmax_ce(l, y, mask)[0], logits)
    kept = [(tuple(a.shape), a.dtype) for a in jax.tree_util.tree_leaves(vjp_fn) if hasattr(a, "shape")]
    assert ((2, 16, vocab), jnp.bfloat16) in kept, kept
    wide_f32 = [k for k in kept if k[0][-1:] == (vocab,) and k[1] == jnp.float32]
    assert not wide_f32, kept
    (grad,) = vjp_fn(jnp.ones(()))
    assert grad.dtype == jnp.bfloat16
