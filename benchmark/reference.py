"""The plain reference: one FedAvg round as Python loops.

For every client, for every step: ``jax.grad`` of the mean masked
cross-entropy, SGD (weight decay, momentum) applied by hand; then the
sample-weighted mean of the clients' variables.  Everything in float32 under
``default_matmul_precision("highest")``; no ``scan``, ``lax.map``, ``vmap`` or
``shard_map``.  A client's step updates its variables in place (donation), so
that the check costs 12 to 16 bytes a parameter and not 36.  It shares
``ModelBundle.apply_train`` (the model's forward pass) with the code under
test and nothing else: not the loss, the optimizer, the local-update loop or
the aggregation."""

from __future__ import annotations

import functools

import numpy as np

# Why these tolerances.  The system computes forward and backward in bf16
# (8 bits of mantissa: each rounding is 2^-8 = 0.4 % relative) against the
# reference's float32 at ``highest``; the roundings of a deep forward +
# backward pass do not cancel, and a few steps of SGD carry them.  At lr 3e-4
# the aggregated delta is also only some hundred float32 ulps of the master
# weights, so the system's own rounding of its mean is part of the error (a
# reference that rounded its mean in the system's order read 1.7 %, this one,
# which averages the clients' exact changes, 2.5 %; the more clients, the
# more).  On the v5e at gpt2-large's widths (depth 8, PR 22; PERF.md has the
# runs) the delta differs by 2.3-2.6 % of its norm with two clients on one
# chip and by 3.2-3.5 % with four on four chips, the loss by 2e-5.  In float32
# on the CPU the two agree to 3e-6 (ResNet rehearsal: momentum, weight decay,
# BatchNorm).  A dropped client moves the delta by a quarter or more, an
# unweighted mean by 10-13 % on the reference cohort, whose clients hold
# different numbers of real samples on purpose (tried in the rehearsal).
# 7 % is twice the most that was seen and under the least a fault causes.
DELTA_REL_L2_TOL = 0.07
LOSS_REL_TOL = 0.01


def system_sample_order(key, round_idx: int, slot_id: int, n: int):
    """The order in which the system's client ``slot_id`` visits its ``n``
    samples in epoch 0 of round ``round_idx``: the program derives all
    randomness from ``fold_in(state.key, round_idx)`` (fedavg.py), so the
    order is an input the reference is handed, like the data."""
    import jax

    k_train = jax.random.fold_in(jax.random.fold_in(key, round_idx), 0)
    ek = jax.random.fold_in(jax.random.fold_in(k_train, slot_id), 0)
    return np.asarray(jax.random.permutation(jax.random.fold_in(ek, 0), n))


def reference_block(config: dict, reference: dict, seed: int):
    """The reduced cohort both sides train: full widths, ``clients`` x
    ``steps`` x ``batch``; every client but the first has its last
    ``batch // 2`` samples masked out as padding, so the clients' weights
    differ.  At batch 1 that is none and they weigh alike: with one sample of
    the second client masked (weights 2 : 1) ``mellum2_silo_code8k`` read a
    delta error of 0.146 for 0.023 (PR 34; PERF.md, section 7: the system
    sums the clients' float32 variables, and a sum over three samples rounds
    where one over two is exact)."""
    from benchmark import traffic

    k, s, b = reference["clients"], reference["steps"], reference["batch"]
    x, y = traffic.make_samples(config, k * s * b, seed + 1)
    mask = np.ones((k, s * b), np.float32)
    mask[1:, s * b - b // 2:] = 0.0
    return (x.reshape(k, s, b, *x.shape[1:]), y.reshape(k, s, b, *y.shape[1:]),
            mask.reshape(k, s, b), mask.sum(axis=1),
            np.ones((k,), np.float32), np.arange(k, dtype=np.int32))


def build_sgd_step(bundle, optimizer: dict):
    """The jitted step of one client: the gradient of the mean masked
    cross-entropy, then SGD with weight decay and momentum written out
    (torch's and optax's form: v = mom * v + g + wd * p; p -= lr * v).
    ``step(cvars, velocity, bx, by, bm)`` updates the client's variables and
    its velocity in place (both donated).  Without momentum there is no
    velocity: pass None and get None, the step is p - lr * (g + wd * p),
    which is what 0 * v + g + wd * p gives to the bit."""
    import jax
    import jax.numpy as jnp

    lr, mom = optimizer["lr"], optimizer.get("momentum", 0.0)
    wd = optimizer.get("weight_decay") or 0.0
    tmap = jax.tree_util.tree_map

    def loss_fn(params, others, bx, by, bm):
        logits, new_vars = bundle.apply_train({**others, "params": params}, bx)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, by[..., None], axis=-1)[..., 0]
        m = jnp.broadcast_to(bm.reshape(bm.shape + (1,) * (nll.ndim - bm.ndim)),
                             nll.shape)
        total = (nll * m).sum()
        return total / jnp.maximum(m.sum(), 1.0), (new_vars, total, m.sum())

    def sgd_step(cvars, velocity, bx, by, bm):
        params = cvars["params"]
        others = {c: v for c, v in cvars.items() if c != "params"}
        (_, (new_vars, total, cnt)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, others, bx, by, bm)
        if mom:
            velocity = tmap(lambda v, gi, p: mom * v + gi + wd * p, velocity,
                            g, params)
            g = velocity
        elif wd:
            g = tmap(lambda gi, p: gi + wd * p, g, params)
        params = tmap(lambda p, v: p - lr * v, params, g)
        return {**new_vars, "params": params}, velocity, total, cnt

    return jax.jit(sgd_step, donate_argnums=(0, 1))


def reference_round(bundle, config: dict, variables, key, round_idx: int,
                    block):
    """(aggregated delta = mean of the clients' variables less ``variables``,
    mean training loss), float32 on the device.  ``variables`` is left as it
    was: every client trains a copy.  Beside it the device holds the delta,
    one client's variables (and its velocity under momentum) and what a step
    holds of its gradient: 12 to 16 bytes a parameter."""
    import jax
    import jax.numpy as jnp

    x, y, mask, num_samples, _, slot_ids = block
    tmap = jax.tree_util.tree_map
    sgd_step = build_sgd_step(bundle, config["optimizer"])
    momentum = bool(config["optimizer"].get("momentum", 0.0))

    @functools.partial(jax.jit, donate_argnums=0)
    def add_weighted(delta, cvars, start, weight):
        # a client's change is exact in float32 (close numbers); adding the
        # clients' variables themselves would round at the weights' ulp,
        # which at lr 3e-4 is a percent of the change
        return tmap(lambda d, c, v: d + weight * (c - v), delta, cvars, start)

    # float32 masters are the system's own arrays here, not copies
    start = tmap(lambda a: jnp.asarray(a, jnp.float32), variables)
    delta, loss_sum, count = tmap(jnp.zeros_like, start), 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for k in range(x.shape[0]):
            n = x.shape[1] * x.shape[2]
            order = system_sample_order(key, round_idx, int(slot_ids[k]), n)
            flat = lambda a: a.reshape(n, *a.shape[2:])[order].reshape(a.shape)
            cx, cy, cm = flat(x[k]), flat(y[k]), flat(mask[k])
            cvars = tmap(jnp.copy, start)  # the steps donate it
            velocity = (tmap(jnp.zeros_like, cvars["params"]) if momentum
                        else None)
            for s in range(x.shape[1]):
                if cm[s].sum() == 0:
                    continue  # a step of padding only changes nothing
                cvars, velocity, total, cnt = sgd_step(
                    cvars, velocity, cx[s], cy[s], cm[s])
                loss_sum += float(total)
                count += float(cnt)
            # the sample-weighted mean, one client at a time
            weight = float(num_samples[k]) / float(np.sum(num_samples))
            delta = add_weighted(delta, cvars, start, weight)
            del cvars, velocity
    return delta, loss_sum / count


def compare(old_variables, system_variables, reference_delta,
            system_loss: float, reference_loss: float) -> dict:
    """Agreement of the aggregated delta (the system's new - old over every
    leaf, as one vector, against the reference's) and of the mean loss."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(old, new, ref):
        f32 = lambda t: [jnp.asarray(l, jnp.float32)  # noqa: E731
                         for l in jax.tree_util.tree_leaves(t)]
        err = sum(jnp.sum(((n - o) - r) ** 2)
                  for n, o, r in zip(f32(new), f32(old), f32(ref)))
        size = sum(jnp.sum(r ** 2) for r in f32(ref))
        finite = jnp.all(jnp.stack([jnp.isfinite(n).all() for n in f32(new)]))
        return err, size, finite

    err, size, finite = norms(old_variables, system_variables,
                              reference_delta)
    delta = float(np.sqrt(float(err) / float(size)))
    loss = abs(system_loss - reference_loss) / abs(reference_loss)
    return {"delta_rel_l2": delta, "loss_rel": loss, "finite": bool(finite),
            "delta_limit": DELTA_REL_L2_TOL, "loss_limit": LOSS_REL_TOL,
            "ok": bool(finite and delta <= DELTA_REL_L2_TOL
                       and loss <= LOSS_REL_TOL)}
