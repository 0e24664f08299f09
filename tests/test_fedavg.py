"""FedAvg engine tests, including the reference's numerical equivalence
oracle (SURVEY.md §4.3): at full participation, full batch, E=1, FedAvg
must equal centralized SGD (reference asserts to 3 decimals via wandb
diffing, ``CI-script-fedavg.sh:42-48``; here we assert on parameters
directly, which is strictly stronger)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.centralized import CentralizedTrainer
from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models.cnn import cnn_dropout
from fedml_tpu.models.linear import logistic_regression


def small_ds(num_clients=4, n=400, partition="homo", seed=0):
    return synthetic_classification(
        num_train=n, num_test=120, input_shape=(16,), num_classes=4,
        num_clients=num_clients, partition=partition, partition_alpha=0.5,
        noise=0.5, seed=seed,
    )


def test_fedavg_learns():
    ds = small_ds()
    bundle = logistic_regression(16, 4)
    cfg = FedAvgConfig(
        num_clients=4, clients_per_round=4, comm_rounds=20, epochs=2,
        batch_size=20, lr=0.3, frequency_of_the_test=100,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)
    first = sim.evaluate_global()
    sim.run()
    last = sim.evaluate_global()
    assert last["test_acc"] > max(first["test_acc"] + 0.2, 0.6)


def test_multi_round_fused_matches_sequential():
    """R rounds fused into one program (make_multi_round_fn) must be
    bit-compatible with R sequential make_round_fn calls: the round
    kernel derives all randomness from fold_in(key, round_idx), so the
    fusion is purely an execution-mode change."""
    from fedml_tpu.algorithms.fedavg import (
        ServerState, make_multi_round_fn, make_round_fn,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.core.sampling import eligible_participation_mask
    from fedml_tpu.core.types import pack_clients

    ds = small_ds()
    bundle = logistic_regression(16, 4)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), epochs=2)
    pack = pack_clients(ds, list(range(4)), batch_size=20)
    key = jax.random.PRNGKey(3)
    state0 = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    args = (
        jnp.asarray(pack.x), jnp.asarray(pack.y), jnp.asarray(pack.mask),
        jnp.asarray(pack.num_samples), jnp.ones(4, jnp.float32),
        jnp.arange(4, dtype=jnp.int32),
    )

    R = 3
    fused = jax.jit(make_multi_round_fn(lu, R))
    f_state, f_metrics = fused(state0, *args)

    single = jax.jit(make_round_fn(lu))
    s_state = state0
    seq_losses = []
    for _ in range(R):
        s_state, m = single(s_state, *args)
        seq_losses.append(float(m["loss_sum"]))

    assert int(f_state.round_idx) == R
    np.testing.assert_allclose(
        np.asarray(f_metrics["loss_sum"]), np.asarray(seq_losses), rtol=1e-6
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        f_state.variables, s_state.variables,
    )

    # on-device subsampling: fused clients_per_round draw == the host
    # applying the same eligibility-aware mask per round
    fused_sub = jax.jit(make_multi_round_fn(lu, R, clients_per_round=2))
    fs_state, fs_metrics = fused_sub(state0, *args)
    s_state = state0
    full = jnp.ones(4, jnp.float32)
    for _ in range(R):
        part = eligible_participation_mask(s_state.key, s_state.round_idx, full, 2)
        assert float(part.sum()) == 2.0
        s_state, m = single(s_state, *(args[:4] + (part, args[5])))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        fs_state.variables, s_state.variables,
    )


def test_eligible_participation_mask_respects_eligibility():
    """The on-device subsampler draws ONLY among participation>0 and can
    never return an empty cohort while any client is eligible (an empty
    draw would zero the weighted average and wipe the global model)."""
    from fedml_tpu.core.sampling import eligible_participation_mask

    key = jax.random.PRNGKey(0)
    base = jnp.array([1, 1, 0, 0, 0, 0, 0, 0], jnp.float32)  # 2 eligible
    for r in range(50):
        m = eligible_participation_mask(key, r, base, 3)
        # never selects an ineligible client, never empty
        assert float((m * (1 - base)).sum()) == 0.0
        assert float(m.sum()) == 2.0  # min(K=3, eligible=2)
    # full eligibility: exactly K distinct
    full = jnp.ones(8, jnp.float32)
    seen = set()
    for r in range(20):
        m = eligible_participation_mask(key, r, full, 3)
        assert float(m.sum()) == 3.0
        seen.add(tuple(np.asarray(m).astype(int)))
    assert len(seen) > 1  # the draw varies by round


def test_partial_run_final_row_has_test_metrics():
    """run(rounds=N) with N != comm_rounds must still end with test
    metrics in its last history row (ADVICE r1: final-round eval keys on
    the loop position, not the absolute round index)."""
    ds = small_ds()
    bundle = logistic_regression(16, 4)
    cfg = FedAvgConfig(
        num_clients=4, clients_per_round=4, comm_rounds=10, epochs=1,
        batch_size=20, lr=0.1, frequency_of_the_test=7,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)
    hist = sim.run(rounds=2)  # round 1: 1 % 7 != 0 and != comm_rounds-1
    assert "test_acc" in hist[-1]
    # resumed second leg ends with test metrics too
    hist2 = sim.run(rounds=2)
    assert "test_acc" in hist2[-1]


def test_fedavg_subsampling_runs():
    ds = small_ds(num_clients=8)
    bundle = logistic_regression(16, 4)
    cfg = FedAvgConfig(
        num_clients=8, clients_per_round=3, comm_rounds=5, epochs=1,
        batch_size=20, lr=0.1, frequency_of_the_test=100,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)
    hist = sim.run()
    assert len(hist) == 5
    assert all(np.isfinite(h["train_loss"]) for h in hist)


def test_equivalence_oracle_fedavg_equals_centralized():
    """Full participation + full batch + E=1 ⇒ FedAvg step == centralized
    full-batch SGD step (sample-weighted grad average == global grad)."""
    ds = small_ds(num_clients=4, n=256, partition="hetero")
    bundle = logistic_regression(16, 4)
    lr = 0.5

    counts = ds.client_sample_counts()
    big_batch = int(counts.max())  # each client: exactly one batch
    cfg = FedAvgConfig(
        num_clients=4, clients_per_round=4, comm_rounds=1, epochs=1,
        batch_size=big_batch, lr=lr, frequency_of_the_test=100, seed=7,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)

    cent = CentralizedTrainer(
        bundle, ds, epochs_per_call=1, batch_size=len(ds.train_x), lr=lr,
        seed=7, shuffle=False,
    )
    # identical init by construction (same bundle.init(PRNGKey(seed)))
    chex_tree_all_close(sim.state.variables, cent.variables)

    sim.run_round()
    cent.train(1)

    chex_tree_all_close(sim.state.variables, cent.variables, atol=2e-5)


def chex_tree_all_close(a, b, atol=1e-6):
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    assert len(leaves_a) == len(leaves_b)
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol, rtol=1e-4)


def test_fedavg_with_dropout_model():
    """Dropout-rng plumbing through the round kernel (per-step keys reach
    apply_train).  Uses a minimal dropout MLP — the full reference
    CNN_DropOut costs ~60 s of XLA compile on this box and its
    construction parity is covered by test_model_parity/test_reference_crossval."""
    import flax.linen as nn

    from fedml_tpu.models.base import ModelBundle

    class TinyDropoutNet(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            return nn.Dense(3)(x)

    ds = synthetic_classification(
        num_train=80, num_test=30, input_shape=(6, 6, 1), num_classes=3,
        num_clients=2, partition="homo", seed=1,
    )
    bundle = ModelBundle(
        module=TinyDropoutNet(), input_shape=(6, 6, 1), needs_dropout_rng=True
    )
    cfg = FedAvgConfig(
        num_clients=2, clients_per_round=2, comm_rounds=2, epochs=1,
        batch_size=16, lr=0.05, frequency_of_the_test=100,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)
    hist = sim.run()
    assert np.isfinite(hist[-1]["train_loss"])


def test_heterogeneous_client_sizes_mask_correct():
    """Clients with very different sizes: padding must not leak into the
    weighted average (weights are true sample counts)."""
    ds = small_ds(num_clients=4, n=400, partition="hetero", seed=2)
    bundle = logistic_regression(16, 4)
    cfg = FedAvgConfig(
        num_clients=4, clients_per_round=4, comm_rounds=3, epochs=1,
        batch_size=16, lr=0.2, frequency_of_the_test=100,
    )
    sim = FedAvgSimulation(bundle, ds, cfg)
    hist = sim.run()
    counts = ds.client_sample_counts()
    assert hist[-1]["count"] == pytest.approx(float(counts.sum()))


def test_fedavg_mixed_precision_bf16():
    """bf16 compute path: masters stay fp32, training still converges,
    and the bf16 model tracks the fp32 model closely on this small task."""
    ds = small_ds()
    bundle = logistic_regression(16, 4)
    kw = dict(
        num_clients=4, clients_per_round=4, comm_rounds=15, epochs=1,
        batch_size=20, lr=0.3, frequency_of_the_test=100,
    )
    sim_bf16 = FedAvgSimulation(bundle, ds, FedAvgConfig(compute_dtype="bf16", **kw))
    sim_fp32 = FedAvgSimulation(bundle, ds, FedAvgConfig(**kw))
    sim_bf16.run()
    sim_fp32.run()
    # master params stayed fp32
    for leaf in jax.tree_util.tree_leaves(sim_bf16.state.variables):
        assert leaf.dtype == jnp.float32
    acc_bf16 = sim_bf16.evaluate_global()["test_acc"]
    acc_fp32 = sim_fp32.evaluate_global()["test_acc"]
    assert acc_bf16 > 0.6
    assert abs(acc_bf16 - acc_fp32) < 0.1


def test_mixed_precision_batchnorm_state_stable():
    """BatchNorm stats must keep fp32 master dtype across the bf16 scan.

    The property lives in make_local_update's tree_cast plumbing, not in
    any particular architecture — a 1-conv BN net exercises it for ~30 s
    less XLA compile than resnet20 on this box (bf16 resnet paths run in
    the slow tier and on the real-TPU bench)."""
    import flax.linen as nn

    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.base import ModelBundle

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Conv(8, (3, 3), use_bias=False)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            return nn.Dense(4)(x.mean(axis=(1, 2)))

    bundle = ModelBundle(module=TinyBN(), input_shape=(8, 8, 3))
    opt = make_client_optimizer("sgd", 0.1)
    lu = make_local_update(bundle, opt, epochs=1, compute_dtype=jnp.bfloat16)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jnp.zeros((2, 4, 8, 8, 3), jnp.float32)
    y = jnp.zeros((2, 4), jnp.int32)
    m = jnp.ones((2, 4), jnp.float32)
    new_vars, metrics = jax.jit(lu.fn)(variables, x, y, m, jax.random.PRNGKey(1))
    ref_dtypes = jax.tree_util.tree_map(lambda v: v.dtype, variables)
    new_dtypes = jax.tree_util.tree_map(lambda v: v.dtype, new_vars)
    assert ref_dtypes == new_dtypes
    assert np.isfinite(float(metrics["loss_sum"]))


def test_failure_injection_exact_exclusion():
    """A client that drops mid-round (participation weight zeroed) is
    EXACTLY excluded: the round result equals a round that never
    sampled it — the elasticity property of masked-psum aggregation."""
    from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.core.sampling import inject_dropout
    from fedml_tpu.core.types import pack_clients

    ds = small_ds(num_clients=4)
    bundle = logistic_regression(16, 4)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), epochs=1)
    round_fn = jax.jit(make_round_fn(lu))
    key = jax.random.PRNGKey(0)
    state = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    pack = pack_clients(ds, [0, 1, 2, 3], batch_size=20)
    args = (jnp.asarray(pack.x), jnp.asarray(pack.y), jnp.asarray(pack.mask),
            jnp.asarray(pack.num_samples))
    ids = jnp.arange(4, dtype=jnp.int32)

    # client 2 dies mid-round
    part_dead = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
    s_dead, _ = round_fn(state, *args, part_dead, ids)
    # oracle: a cohort that never contained client 2 (global slot ids
    # keep per-client RNG streams identical across the two packings)
    steps = pack.x.shape[1]
    pack3 = pack_clients(ds, [0, 1, 3], batch_size=20, steps_per_epoch=steps)
    s_never, _ = round_fn(
        state,
        jnp.asarray(pack3.x), jnp.asarray(pack3.y), jnp.asarray(pack3.mask),
        jnp.asarray(pack3.num_samples),
        jnp.ones(3, jnp.float32),
        jnp.asarray([0, 1, 3], jnp.int32),
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        s_dead.variables, s_never.variables,
    )
    # and differs from the full-cohort round
    s_full, _ = round_fn(state, *args, jnp.ones(4, jnp.float32), ids)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), s_dead.variables,
        s_full.variables,
    )
    assert max(jax.tree_util.tree_leaves(diffs)) > 0

    # inject_dropout: deterministic, keeps at least one participant
    m = inject_dropout(key, 3, jnp.ones(4, jnp.float32), drop_prob=0.5)
    m2 = inject_dropout(key, 3, jnp.ones(4, jnp.float32), drop_prob=0.5)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
    all_dead = inject_dropout(key, 1, jnp.ones(4, jnp.float32), drop_prob=1.0)
    assert float(all_dead.sum()) == 1.0


def test_run_fused_matches_run():
    """run_fused (make_multi_round_fn between evals) must be
    bit-identical to the per-round dispatch loop in the
    full-participation regime — same kernel, same (key, round_idx)
    randomness, device-resident round-independent cohort block."""
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models.linear import logistic_regression

    ds = synthetic_classification(
        num_train=120, num_test=40, input_shape=(12,), num_classes=3,
        num_clients=4, partition="hetero", seed=5,
    )
    cfg = FedAvgConfig(num_clients=4, clients_per_round=4, comm_rounds=5,
                       epochs=1, batch_size=8, lr=0.2, seed=5,
                       frequency_of_the_test=2)
    bundle = logistic_regression(12, 3)
    a = FedAvgSimulation(bundle, ds, cfg)
    a.run()
    b = FedAvgSimulation(bundle, ds, cfg)
    b.run_fused()

    for la, lb in zip(jax.tree_util.tree_leaves(a.state.variables),
                      jax.tree_util.tree_leaves(b.state.variables)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # per-round train metrics identical; eval rows land on the same
    # rounds with the same VALUES (an eval computed from a mid-chunk
    # divergent state would differ here even if the final state agrees)
    for ra, rb in zip(a.history, b.history):
        assert ra["round"] == rb["round"]
        np.testing.assert_allclose(ra["loss_sum"], rb["loss_sum"], rtol=1e-6)
        assert ("test_acc" in ra) == ("test_acc" in rb)
        if "test_acc" in ra:
            np.testing.assert_allclose(ra["test_acc"], rb["test_acc"],
                                       rtol=1e-6)


def test_run_fused_sampled_matches_run():
    """The scheduled-cohort fused driver (host pre-draws R cohorts, one
    device call per chunk) must be bit-identical to the per-round
    dispatch loop in the SAMPLED cross-device regime — same
    host_sample_ids stream, same pack seeds, same per-round dropout
    draw (VERDICT r3 weak #7)."""
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models.linear import logistic_regression

    ds = synthetic_classification(
        num_train=600, num_test=40, input_shape=(12,), num_classes=3,
        num_clients=20, partition="power_law", seed=5,
    )
    cfg = FedAvgConfig(num_clients=20, clients_per_round=4, comm_rounds=7,
                       epochs=1, batch_size=8, lr=0.2, seed=5,
                       frequency_of_the_test=3, drop_prob=0.3)
    bundle = logistic_regression(12, 3)
    a = FedAvgSimulation(bundle, ds, cfg)
    a.run()
    b = FedAvgSimulation(bundle, ds, cfg)
    b.run_fused_sampled(rounds_per_call=3)

    for la, lb in zip(jax.tree_util.tree_leaves(a.state.variables),
                      jax.tree_util.tree_leaves(b.state.variables)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for ra, rb in zip(a.history, b.history):
        assert ra["round"] == rb["round"]
        np.testing.assert_allclose(ra["loss_sum"], rb["loss_sum"], rtol=1e-6)
        assert ("test_acc" in ra) == ("test_acc" in rb)
        if "test_acc" in ra:
            np.testing.assert_allclose(ra["test_acc"], rb["test_acc"],
                                       rtol=1e-6)

    # the robust subclass's per-round poison swap is honored through
    # _cohort_block; its _build_round_fn is the base one, so the
    # scheduled driver must match its run() too
    from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustSimulation

    rcfg = FedAvgConfig(num_clients=6, clients_per_round=3, comm_rounds=4,
                        epochs=1, batch_size=8, lr=0.2, seed=2,
                        frequency_of_the_test=2)
    ra_ = FedAvgRobustSimulation(
        bundle, ds, rcfg, defense_type="norm_diff_clipping",
        norm_bound=0.5, attacker_client=1, attack_freq=2,
    )
    ra_.run()
    rb_ = FedAvgRobustSimulation(
        bundle, ds, rcfg, defense_type="norm_diff_clipping",
        norm_bound=0.5, attacker_client=1, attack_freq=2,
    )
    rb_.run_fused_sampled(rounds_per_call=2)
    for la, lb in zip(jax.tree_util.tree_leaves(ra_.state.variables),
                      jax.tree_util.tree_leaves(rb_.state.variables)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert [r.get("attacking") for r in ra_.history] == \
        [r.get("attacking") for r in rb_.history]

    # run_fused (resident-cohort form) still refuses the sampled regime
    import pytest

    c = FedAvgSimulation(bundle, ds, FedAvgConfig(
        num_clients=4, clients_per_round=2, comm_rounds=2, epochs=1,
        batch_size=8, seed=5))
    with pytest.raises(ValueError, match="full-participation"):
        c.run_fused()


def test_synthetic_label_noise_ceiling():
    """label_noise=η flips exactly ~η of labels to WRONG classes: a
    perfect prototype classifier scores ≈ 1−η, giving the convergence
    artifact a documented sub-1.0 ceiling."""
    import numpy as np

    from fedml_tpu.data.synthetic import synthetic_classification

    ds = synthetic_classification(
        num_train=4000, num_test=4000, input_shape=(6,), num_classes=4,
        num_clients=4, noise=0.05, label_noise=0.2, seed=3,
    )
    # tight clusters (noise=0.05): nearest-prototype = the CLEAN label
    rng = np.random.RandomState(3)
    protos = rng.normal(0, 1, (4, 6)).astype(np.float32)
    d = ((ds.test_x[:, None, :] - protos[None]) ** 2).sum(-1)
    clean_pred = d.argmin(1)
    acc = float((clean_pred == ds.test_y).mean())
    assert 0.75 < acc < 0.85  # ceiling ≈ 1 - η = 0.8
    flipped = float((clean_pred != ds.test_y).mean())
    assert 0.15 < flipped < 0.25


def test_run_fused_checkpoint_resume(tmp_path):
    """Checkpoint mid-run, rebuild the simulation fresh, restore, and
    continue with run_fused: the final state must be bit-identical to an
    uninterrupted run (the convergence driver's crash recovery path —
    tools/convergence_run.py --checkpoint-dir)."""
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.core.checkpoint import CheckpointManager
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models.linear import logistic_regression

    ds = synthetic_classification(
        num_train=120, num_test=40, input_shape=(10,), num_classes=3,
        num_clients=4, partition="hetero", seed=9,
    )
    cfg = FedAvgConfig(num_clients=4, clients_per_round=4, comm_rounds=6,
                       epochs=1, batch_size=8, lr=0.2, seed=9,
                       frequency_of_the_test=2)
    bundle = logistic_regression(10, 3)

    ref = FedAvgSimulation(bundle, ds, cfg)
    ref.run_fused()

    a = FedAvgSimulation(bundle, ds, cfg)
    a.run_fused(rounds=3)  # interrupted after round 2
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
    mgr.save(3, a.state)

    b = FedAvgSimulation(bundle, ds, cfg)  # fresh process analogue
    b.state = mgr.restore(like=b.state)
    assert int(b.state.round_idx) == 3
    b.run_fused(rounds=cfg.comm_rounds - 3)

    for la, lb in zip(jax.tree_util.tree_leaves(ref.state.variables),
                      jax.tree_util.tree_leaves(b.state.variables)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # resumed eval cadence keys on ABSOLUTE rounds: same eval rounds as
    # the uninterrupted run's tail
    ref_evals = [h["round"] for h in ref.history if "test_acc" in h]
    b_evals = [h["round"] for h in b.history if "test_acc" in h]
    assert [r for r in ref_evals if r >= 3] == b_evals


def test_prebuilt_shard_map_kernel_refuses_on_device_sampling():
    """ADVICE r5: make_round_fn tags its kernel with the baked-in
    axis_name; a pre-built shard_map kernel handed to a fused driver
    together with on-device subsampling/dropout must raise — under
    shard_map each device sees only its local client block, so the
    draw would silently be per-device-local."""
    from fedml_tpu.algorithms.fedavg import (
        make_multi_round_fn, make_round_fn, make_scheduled_multi_round_fn,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    bundle = logistic_regression(16, 4)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), epochs=1)

    plain = make_round_fn(lu)
    assert plain.axis_name is None
    sharded = make_round_fn(lu, axis_name="clients")
    assert sharded.axis_name == "clients"
    # every pre-built kernel family carries the tag, not just FedAvg's
    from fedml_tpu.algorithms.fednova import make_fednova_round_fn

    nova = make_fednova_round_fn(lu, lr=0.1, momentum=0.0,
                                 axis_name="clients")
    assert nova.axis_name == "clients"

    # the sharded kernel still fuses fine WITHOUT on-device sampling
    make_multi_round_fn(None, 2, round_fn=sharded)
    # ... and the plain kernel still takes on-device sampling
    make_multi_round_fn(None, 2, clients_per_round=2, round_fn=plain)

    with pytest.raises(ValueError, match="shard_map"):
        make_multi_round_fn(None, 2, clients_per_round=2, round_fn=sharded)
    with pytest.raises(ValueError, match="shard_map"):
        make_multi_round_fn(None, 2, drop_prob=0.5, round_fn=sharded)
    # kwarg-built path keeps the original guard through the same check
    with pytest.raises(ValueError, match="shard_map"):
        make_multi_round_fn(lu, 2, clients_per_round=2, axis_name="clients")
    # scheduled driver: its host-keyed dropout has the same local-block
    # hazard
    with pytest.raises(ValueError, match="shard_map"):
        make_scheduled_multi_round_fn(None, drop_prob=0.5, round_fn=sharded)
    make_scheduled_multi_round_fn(None, round_fn=sharded)


# --- the weighted sum folded into the client loop (ISSUE 27) -----------------

_FOLD_K = 3


def _fold_setup():
    """K = 3 clients of unequal size on a linear model, fp32."""
    from fedml_tpu.algorithms.fedavg import ServerState
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    bundle = logistic_regression(16, 4)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 0.1), epochs=1)
    key = jax.random.PRNGKey(5)
    state = ServerState(bundle.init(key), (), jnp.zeros((), jnp.int32), key)
    steps, b = 2, 5
    kx, ky = jax.random.split(jax.random.PRNGKey(6))
    args = (
        jax.random.normal(kx, (_FOLD_K, steps, b, 16), jnp.float32),
        jax.random.randint(ky, (_FOLD_K, steps, b), 0, 4),
        jnp.ones((_FOLD_K, steps, b), jnp.float32),
        jnp.asarray((30, 50, 20), jnp.float32),
    )
    return lu, state, args, jnp.arange(_FOLD_K, dtype=jnp.int32)


@pytest.mark.parametrize("part", [(1.0, 0.0, 1.0), (1.0, 1.0, 1.0)],
                         ids=["one_dropped", "all_report"])
def test_folded_round_equals_stacked_einsum_and_sequential_sum(part):
    """The client loop's carry is the weighted sum: equal to the stacked
    einsum to rounding, and bit for bit to acc += w_k * v_k in client
    order.  A dropped client weighs nothing."""
    from fedml_tpu.algorithms.fedavg import make_round_fn

    lu, state, args, slot_ids = _fold_setup()
    part = jnp.asarray(part)
    new_state, metrics = jax.jit(make_round_fn(lu))(
        state, *args, part, slot_ids)
    assert float(metrics["participants"]) == float(part.sum())

    # each client's trained model, from local_update with the round's keys
    k_train = jax.random.fold_in(jax.random.fold_in(state.key, 0), 0)
    x, y, mask, num_samples = args
    one = jax.jit(lambda *a: lu(*a)[0])
    trained = [
        one(state.variables, x[k], y[k], mask[k],
            jax.random.fold_in(k_train, k))
        for k in range(_FOLD_K)
    ]
    w = np.asarray(part, np.float32) * np.asarray(num_samples, np.float32)
    den = np.float32(w.sum())
    # compiled, as the round is: XLA's CPU backend contracts a + w * v into
    # one fused multiply-add, which numpy's two roundings do not reproduce
    step = jax.jit(lambda acc, w_k, v_k: acc + w_k * v_k)
    leaves = [jax.tree_util.tree_leaves(t) for t in trained]
    order_shows = False
    for i, got in enumerate(jax.tree_util.tree_leaves(new_state.variables)):
        rows = np.stack([np.asarray(l[i], np.float32) for l in leaves])
        stacked = np.einsum("k,k...->...", w, rows) / den
        np.testing.assert_allclose(np.asarray(got), stacked, rtol=1e-6)

        def seq_sum(order):
            acc = np.zeros(rows.shape[1:], np.float32)
            for k in order:
                acc = np.asarray(step(acc, w[k], rows[k]))
            return acc

        acc = seq_sum(range(_FOLD_K))
        np.testing.assert_array_equal(np.asarray(got), acc / den)
        order_shows |= bool((seq_sum(reversed(range(_FOLD_K))) != acc).any())
    # the pin is sharp: clients taken in another order give another sum
    assert order_shows


def _lower_fold_case(case):
    from fedml_tpu.algorithms.fedavg import make_round_fn
    from fedml_tpu.compress import get_codec
    from fedml_tpu.parallel.spmd import make_client_mesh, make_spmd_round_fn

    lu, state, args, slot_ids = _fold_setup()
    if case == "spmd_axis_name":
        fn = make_spmd_round_fn(make_client_mesh(1), lu)
    else:
        fn = jax.jit(make_round_fn(lu, **{
            "plain": {},
            "client_unroll": {"client_unroll": 2},
            "codec": {"codec": get_codec("int8")},
            "aggregate_transform": {
                "aggregate_transform": lambda old, stacked, w, rngs: stacked},
            "vmap": {"client_axis_impl": "vmap"},
        }[case]))
    text = fn.lower(state, *args, jnp.ones(_FOLD_K), slot_ids).as_text()
    # (leaves whose fp32 [K, ...] stack is a tensor of the program, all leaves)
    shapes = [l.shape for l in jax.tree_util.tree_leaves(state.variables)]
    return [
        shape for shape in shapes
        if "tensor<%sxf32>" % "x".join(map(str, (_FOLD_K,) + shape)) in text
    ], shapes


@pytest.mark.parametrize("case", ["plain", "client_unroll", "spmd_axis_name"])
def test_folded_round_holds_no_client_stack(case):
    stacked, _ = _lower_fold_case(case)
    assert stacked == []


@pytest.mark.parametrize("case", ["codec", "aggregate_transform", "vmap"])
def test_stack_stays_for_what_reads_it_whole(case):
    stacked, every_leaf = _lower_fold_case(case)
    assert stacked == every_leaf


def test_folded_round_all_clients_dropped_is_a_no_op():
    from fedml_tpu.algorithms.fedavg import make_round_fn

    lu, state, args, slot_ids = _fold_setup()
    new_state, metrics = jax.jit(make_round_fn(lu))(
        state, *args, jnp.zeros(_FOLD_K), slot_ids)
    assert float(metrics["participants"]) == 0.0
    assert int(new_state.round_idx) == 1
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        new_state.variables, state.variables,
    )
