"""The plain reference after PR 34 (in-place client steps, no velocity where
none is used, the system's new state on the host during the check): the same
delta and loss as the form before it, to the bit; a session that the check
leaves whole; a reference cohort whose clients weigh differently; and a check
that fails when the timed path is broken underneath it."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, reference, run

WORKLOADS = ["gpt2l_silo_fused", "gpt2l_silo_spmd4", "mellum2_silo_code8k"]


def reference_round_before(bundle, config, variables, key, round_idx, block):
    """``reference.reference_round`` as it stood before PR 34, kept here as
    what the new one must equal: no donation, a velocity tree always."""
    opt = config["optimizer"]
    lr, mom = opt["lr"], opt.get("momentum", 0.0)
    wd = opt.get("weight_decay") or 0.0
    x, y, mask, num_samples, _, slot_ids = block
    tmap = jax.tree_util.tree_map

    def loss_fn(params, others, bx, by, bm):
        logits, new_vars = bundle.apply_train({**others, "params": params}, bx)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, by[..., None], axis=-1)[..., 0]
        m = jnp.broadcast_to(bm.reshape(bm.shape + (1,) * (nll.ndim - bm.ndim)),
                             nll.shape)
        total = (nll * m).sum()
        return total / jnp.maximum(m.sum(), 1.0), (new_vars, total, m.sum())

    @jax.jit
    def sgd_step(cvars, velocity, bx, by, bm):
        others = {c: v for c, v in cvars.items() if c != "params"}
        (_, (new_vars, total, cnt)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(cvars["params"], others, bx, by, bm)
        velocity = tmap(lambda v, gi, p: mom * v + gi + wd * p, velocity, g,
                        cvars["params"])
        params = tmap(lambda p, v: p - lr * v, cvars["params"], velocity)
        return {**new_vars, "params": params}, velocity, total, cnt

    @jax.jit
    def add_weighted(delta, cvars, start, weight):
        return tmap(lambda d, c, v: d + weight * (c - v), delta, cvars, start)

    start = tmap(lambda a: jnp.asarray(a, jnp.float32), variables)
    delta, loss_sum, count = tmap(jnp.zeros_like, start), 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for k in range(x.shape[0]):
            n = x.shape[1] * x.shape[2]
            order = reference.system_sample_order(
                key, round_idx, int(slot_ids[k]), n)
            flat = lambda a: a.reshape(n, *a.shape[2:])[order].reshape(a.shape)
            cx, cy, cm = flat(x[k]), flat(y[k]), flat(mask[k])
            cvars = start
            velocity = tmap(jnp.zeros_like, cvars["params"])
            for s in range(x.shape[1]):
                if cm[s].sum() == 0:
                    continue
                cvars, velocity, total, cnt = sgd_step(
                    cvars, velocity, cx[s], cy[s], cm[s])
                loss_sum += float(total)
                count += float(cnt)
            weight = float(num_samples[k]) / float(np.sum(num_samples))
            delta = add_weighted(delta, cvars, start, weight)
    return delta, loss_sum / count


def lm_job(workload, reference_shape):
    """A language-model cell's rehearsal in float32: (bundle the check is
    handed, config, block, the program's init)."""
    cell = cells.load_cell(workload, rehearsal=True)
    config = {**cell.config, "compute_dtype": "fp32"}
    family = cells.load_family(config)
    program = family.build_bundle(config)
    bundle = (family.plain_bundle(config) if hasattr(family, "plain_bundle")
              else program)
    block = reference.reference_block(
        config, {**cell.reference, **reference_shape}, seed=11)
    return bundle, config, block, program.init


def resnet_job():
    """Momentum, weight decay and BatchNorm statistics: ResNet-20 on 16 x 16
    images, 2 clients x 2 steps x batch 4, the second client's last two
    samples padding."""
    from fedml_tpu.models.resnet import resnet20

    k, s, b = 2, 2, 4
    rng = np.random.default_rng(5)
    x = rng.standard_normal((k, s, b, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 10, (k, s, b)).astype(np.int32)
    mask = np.ones((k, s * b), np.float32)
    mask[1:, -2:] = 0.0
    block = (x, y, mask.reshape(k, s, b), mask.sum(axis=1),
             np.ones((k,), np.float32), np.arange(k, dtype=np.int32))
    config = {"optimizer": {"name": "sgd", "lr": 0.05, "momentum": 0.9,
                            "weight_decay": 5e-4}}
    bundle = resnet20(num_classes=10, image_size=16)
    return bundle, config, block, bundle.init


JOBS = {
    "transformer_lm": lambda: lm_job("gpt2l_silo_fused", {}),
    "decoder_experts_batch1": lambda: lm_job("mellum2_silo_code8k",
                                             {"batch": 1}),
    "resnet_momentum_wd_batchnorm": resnet_job,
}


@pytest.mark.parametrize("job", sorted(JOBS))
def test_the_round_equals_the_form_before_it_to_the_bit(job):
    bundle, config, block, init = JOBS[job]()
    key = jax.random.PRNGKey(2**31 + 7)
    variables = jax.jit(init)(key)
    kept = jax.tree_util.tree_map(np.array, variables)
    want_delta, want_loss = reference_round_before(
        bundle, config, variables, key, 3, block)
    got_delta, got_loss = reference.reference_round(
        bundle, config, variables, key, 3, block)
    assert got_loss == want_loss
    want, got = (jax.tree_util.tree_leaves_with_path(t)
                 for t in (want_delta, got_delta))
    assert [p for p, _ in want] == [p for p, _ in got]
    for (path, w), (_, g) in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g)), path
    assert any(np.asarray(w).any() for _, w in want)
    # the state it was handed is whole, though the steps donate
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(variables)):
        assert np.array_equal(a, np.asarray(b))


@pytest.mark.parametrize("momentum, trees", [(0.0, 1), (0.9, 2)])
def test_no_velocity_tree_without_momentum(momentum, trees):
    bundle, config, block, init = lm_job("gpt2l_silo_fused", {})
    variables = jax.jit(init)(jax.random.PRNGKey(0))
    leaves = len(jax.tree_util.tree_leaves(variables))
    step = reference.build_sgd_step(
        bundle, {"lr": 0.1, "momentum": momentum, "weight_decay": 0.01})
    velocity = variables["params"] if momentum else None
    x, y, mask = (a[0, 0] for a in block[:3])
    lowered = step.lower(variables, velocity, x, y, mask)
    n_in = len(jax.tree_util.tree_leaves(lowered.in_avals))
    assert n_in == trees * leaves + 3
    # every leaf of the client (and of its velocity) is donated
    donated = [a.donated for a in jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda a: hasattr(a, "donated"))]
    assert sum(donated) == trees * leaves


@pytest.mark.parametrize("batch, padded", [(1, 0), (2, 1), (4, 2), (5, 2)])
def test_later_clients_hold_padding_from_batch_2_on(batch, padded):
    cell = cells.load_cell("gpt2l_silo_fused", rehearsal=True)
    shape = {"clients": 3, "steps": 2, "batch": batch}
    mask, num_samples = reference.reference_block(cell.config, shape, 4)[2:4]
    assert mask[0].all() and num_samples[0] == 2 * batch
    for k in (1, 2):
        assert num_samples[k] == 2 * batch - padded
        assert mask[k].reshape(-1)[:2 * batch - padded].all()
        assert not mask[k].reshape(-1)[2 * batch - padded:].any()


@pytest.fixture(scope="module", params=WORKLOADS)
def checked_session(request):
    """A rehearsal session after two calls, with what ``check_reference``
    said of it and its state as it was before the check."""
    cell = cells.load_cell(request.param, rehearsal=True)
    if len(jax.devices()) < cell.chips:
        pytest.skip(f"{cell.chips} devices needed")
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 2**31 + 3, jax.devices()[:cell.chips])
    for _ in range(2):
        session.call()
    before = jax.tree_util.tree_map(np.array, session.state)
    agreement = run.check_reference(cell, session, 2**31 + 3)
    return cell, session, before, agreement


def test_the_check_passes_and_leaves_the_session_whole(checked_session):
    cell, session, before, agreement = checked_session
    assert agreement["ok"], agreement
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(session.state)):
        assert np.array_equal(a, np.asarray(b))
    idx = session.round_idx()
    rounds, metrics = session.call()  # no leaf of the state was donated away
    assert session.round_idx() == idx + rounds
    assert run.call_ok(metrics, session.cohort)


class Unweighted:
    """A session whose reference round weighs every client alike."""

    def __init__(self, session):
        self.session = session

    def __getattr__(self, name):
        return getattr(self.session, name)

    def reference_round(self, block):
        x, y, mask, num_samples, part, slots = block
        new_vars, metrics = self.session.reference_round(
            (x, y, mask, np.ones_like(num_samples), part, slots))
        return new_vars, metrics


def test_an_unweighted_mean_fails():
    # at the rehearsal's batch 2; at the cell's batch 1 the clients weigh
    # alike and the fault is not tried (reference_block says why)
    cell = cells.load_cell("mellum2_silo_code8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 9, jax.devices()[:1])
    sound = run.check_reference(cell, session, 9)
    fault = run.check_reference(cell, Unweighted(session), 9)
    assert sound["ok"] and sound["delta_rel_l2"] < 0.01, sound
    assert not fault["ok"] and fault["delta_rel_l2"] > 1.5 * 0.07, fault


def unchanged(round_fn):
    """The round program, returning the state it was given."""
    def broken(state, *block):
        _, metrics = round_fn(state, *block)
        return state, metrics
    return broken


def half_batch(round_fn):
    """The round program over the first half of every step's batch, the
    mean taken over that half."""
    def broken(state, x, y, mask, *rest):
        mask = jnp.asarray(mask).at[:, :, mask.shape[2] // 2:].set(0.0)
        return round_fn(state, x, y, mask, *rest)
    return broken


@pytest.mark.parametrize("fault", [None, unchanged, half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_a_run_on_a_broken_timed_path_is_not_correct(fault, monkeypatch,
                                                     capsys):
    from benchmark.drivers import fused

    if fault is not None:
        build = fused.build_round_fn
        monkeypatch.setattr(fused, "build_round_fn",
                            lambda *a, **k: fault(build(*a, **k)))
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "gpt2l_silo_fused", "--seed", "21",
        "--seconds", "0.3", "--rehearsal"])
    rc = run.main()  # the rehearsal skips the look for a chip, nothing else
    said = capsys.readouterr().out
    assert rc == (0 if fault is None else 1)
    assert f"correct {fault is None}" in said.splitlines()[0]
