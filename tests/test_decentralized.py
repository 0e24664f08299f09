"""Topology + gossip + DOL tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from fedml_tpu.algorithms.decentralized import (
    DecentralizedSimulation,
    dense_mix,
    make_gossip_round_fn,
)
from fedml_tpu.algorithms.decentralized_online import (
    make_stream,
    run_dsgd,
    run_pushsum,
)
from fedml_tpu.core.topology import (
    AsymmetricTopologyManager,
    SymmetricTopologyManager,
    ring_topology,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models.linear import logistic_regression


def test_symmetric_topology_row_stochastic_and_symmetric_support():
    tm = SymmetricTopologyManager(8, neighbor_num=3, seed=0)
    w = tm.generate_topology()
    np.testing.assert_allclose(w.sum(axis=1), np.ones(8), atol=1e-9)
    assert ((w > 0) == (w > 0).T).all()  # symmetric support
    assert all(w[i, i] > 0 for i in range(8))
    assert tm.get_in_neighbor_idx_list(0)  # ring guarantees neighbors


def test_asymmetric_topology_row_stochastic():
    tm = AsymmetricTopologyManager(8, undirected_neighbor_num=4, seed=1)
    w = tm.generate_topology()
    np.testing.assert_allclose(w.sum(axis=1), np.ones(8), atol=1e-9)


def test_ring_topology():
    w = ring_topology(5)
    np.testing.assert_allclose(w.sum(axis=1), np.ones(5))
    assert w[0, 1] > 0 and w[0, 4] > 0 and w[0, 2] == 0


def test_dense_mix_consensus():
    """Repeated mixing with a connected doubly-stochastic matrix drives
    workers to consensus at the average."""
    w = jnp.asarray(ring_topology(4), jnp.float32)
    vals = {"p": jnp.asarray([[1.0], [2.0], [3.0], [10.0]])}
    for _ in range(200):
        vals = dense_mix(vals, w)
    np.testing.assert_allclose(np.asarray(vals["p"]).ravel(), np.full(4, 4.0), atol=1e-3)


def test_gossip_simulation_learns_and_converges():
    ds = synthetic_classification(
        num_train=600, num_test=150, input_shape=(12,), num_classes=3,
        num_clients=6, partition="hetero", partition_alpha=0.5, noise=0.5, seed=0,
    )
    tm = SymmetricTopologyManager(6, neighbor_num=2, seed=0)
    sim = DecentralizedSimulation(
        logistic_regression(12, 3), ds, tm.generate_topology(),
        epochs=1, batch_size=20, lr=0.2,
    )
    acc0 = sim.evaluate_worker(0)["test_acc"]
    d0 = None
    sim.run(10)
    accs = [sim.evaluate_worker(i)["test_acc"] for i in range(6)]
    assert min(accs) > acc0
    # gossip keeps workers near consensus
    assert sim.consensus_distance() < 1.0


def test_gossip_spmd_ring_matches_dense_ring():
    """ppermute ring mixing == dense ring-matrix mixing (one client/device)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = 4
    ds = synthetic_classification(
        num_train=200, num_test=50, input_shape=(8,), num_classes=2,
        num_clients=n, partition="homo", seed=0,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.core.types import pack_clients

    bundle = logistic_regression(8, 2)
    opt = make_client_optimizer("sgd", 0.1)
    lu = make_local_update(bundle, opt, epochs=1)
    pack = pack_clients(ds, list(range(n)), batch_size=16, seed=0)
    init = bundle.init(jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(lambda l: jnp.stack([l] * n), init)
    rng = jax.random.PRNGKey(1)
    ids = jnp.arange(n, dtype=jnp.int32)
    args = (jnp.asarray(pack.x), jnp.asarray(pack.y), jnp.asarray(pack.mask))

    dense_fn = jax.jit(make_gossip_round_fn(lu, ring_topology(n)))
    ref_vars, _ = dense_fn(stacked, *args, rng, ids)

    mesh = Mesh(np.array(jax.devices()[:n]), ("clients",))
    ring_fn = jax.jit(
        shard_map(
            make_gossip_round_fn(lu, None, axis_name="clients", ring=True),
            mesh=mesh,
            in_specs=(P("clients"), P("clients"), P("clients"), P("clients"), P(), P("clients")),
            out_specs=(P("clients"), P()),
            check_vma=False,
        )
    )
    shard = NamedSharding(mesh, P("clients"))
    sharded_stacked = jax.device_put(stacked, shard)
    got_vars, _ = ring_fn(
        sharded_stacked,
        *(jax.device_put(a, shard) for a in args),
        jax.device_put(rng, NamedSharding(mesh, P())),
        jax.device_put(ids, shard),
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_vars), jax.tree_util.tree_leaves(got_vars)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_dol_dsgd_regret_decreases():
    xs, ys = make_stream(400, 6, 10, seed=0)
    w = SymmetricTopologyManager(6, neighbor_num=2, seed=0).generate_topology()
    res = run_dsgd(xs, ys, w, lr=0.3)
    assert res.regret_curve[-1] < res.regret_curve[20] * 0.7
    assert res.consensus_distance < 1.0


def test_dol_pushsum_handles_asymmetric():
    xs, ys = make_stream(400, 6, 10, seed=1)
    tm = AsymmetricTopologyManager(6, undirected_neighbor_num=3, seed=2)
    res = run_pushsum(xs, ys, tm.generate_topology(), lr=0.3)
    assert res.regret_curve[-1] < res.regret_curve[20] * 0.7
    assert np.isfinite(res.final_params).all()
