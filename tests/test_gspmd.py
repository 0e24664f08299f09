"""DP×TP federated round on a 2-D (dp, mp) mesh: the rule engine's
``make_rule_round_fn`` under the ``fedllm`` table.

Oracle: the GSPMD-partitioned round equals the same round function run
unsharded on one device (the parallelism-equivalence strategy of
tests/test_tensor_pipeline.py applied to the full FL round)."""

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
from fedml_tpu.core.client import make_client_optimizer, make_local_update
from fedml_tpu.core.types import pack_clients
from fedml_tpu.data.shakespeare import load_fed_shakespeare
from fedml_tpu.models.transformer import transformer_lm
from fedml_tpu.parallel.mesh import make_dp_mp_mesh
from fedml_tpu.parallel.partition import FEDLLM_RULES, make_rule_round_fn


def _setup(num_clients=4, seq_len=80):
    # per-position targets; /nonexistent forces the synthetic stand-in
    # even when real data was downloaded (cf. tests/test_data.py)
    ds = load_fed_shakespeare(data_dir="/nonexistent", num_clients=num_clients)
    bundle = transformer_lm(
        vocab_size=128, embed_dim=32, num_heads=4, num_layers=2,
        seq_len=seq_len,
    )
    opt = make_client_optimizer("sgd", 0.1)
    local_update = make_local_update(bundle, opt, epochs=1)
    pack = pack_clients(ds, list(range(num_clients)), batch_size=4,
                        steps_per_epoch=2)
    key = jax.random.PRNGKey(0)
    state = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    args = (
        pack.x, pack.y, pack.mask, pack.num_samples,
        np.ones(num_clients, np.float32),
        np.arange(num_clients, dtype=np.int32),
    )
    return bundle, local_update, state, args


def test_dp_tp_round_matches_single_device():
    bundle, local_update, state, args = _setup()
    # single-device oracle (identical round code, vmap client axis)
    ref_fn = jax.jit(make_round_fn(local_update, client_axis_impl="vmap"))
    ref_state, ref_metrics = ref_fn(state, *[jnp.asarray(a) for a in args])

    mesh = make_dp_mp_mesh(2, 4)  # 2-way client DP x 4-way TP
    round_fn, shard_state, shard_data = make_rule_round_fn(
        mesh, local_update, state.variables, FEDLLM_RULES
    )
    new_state, metrics = round_fn(shard_state(state), *shard_data(args))

    assert int(new_state.round_idx) == 1
    np.testing.assert_allclose(
        float(metrics["loss_sum"]), float(ref_metrics["loss_sum"]),
        rtol=1e-4,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        ),
        new_state.variables,
        ref_state.variables,
    )


def test_dp_tp_params_sharded_over_model_axis():
    _, local_update, state, args = _setup()
    mesh = make_dp_mp_mesh(2, 4)
    round_fn, shard_state, shard_data = make_rule_round_fn(
        mesh, local_update, state.variables, FEDLLM_RULES
    )
    st = shard_state(state)
    qkv = st.variables["params"]["Block_0"]["MultiHeadAttention_0"]["Dense_0"]["kernel"]
    assert qkv.sharding.spec == P(None, "mp")
    # round output preserves the TP layout (no silent re-replication)
    new_state, _ = round_fn(st, *shard_data(args))
    qkv2 = new_state.variables["params"]["Block_0"]["MultiHeadAttention_0"]["Dense_0"]["kernel"]
    assert qkv2.sharding.spec == P(None, "mp")


def test_dp_tp_fedadam_server_opt_state_sharded():
    """FedAdam moments mirror the params, so their sharding must follow
    the TP plan rather than be replicated (bigger-than-one-chip server
    state)."""
    from fedml_tpu.algorithms.fedopt import make_fedopt_server_update
    from fedml_tpu.core.optrepo import get_server_optimizer

    _, local_update, state, args = _setup()
    server_opt = get_server_optimizer("adam", lr=0.01)
    opt_state = server_opt.init(state.variables["params"])
    state = ServerState(
        variables=state.variables, opt_state=opt_state,
        round_idx=state.round_idx, key=state.key,
    )
    mesh = make_dp_mp_mesh(2, 4)
    round_fn, shard_state, shard_data = make_rule_round_fn(
        mesh, local_update, state.variables, FEDLLM_RULES,
        server_update=make_fedopt_server_update(server_opt),
        opt_state_template=opt_state,
    )
    st = shard_state(state)
    # find the adam mu for a column-parallel kernel and check its layout
    mu = None
    for s in jax.tree_util.tree_leaves(st.opt_state):
        if s.ndim == 2 and s.shape[1] == 3 * 32:  # qkv moment [E, 3E]
            mu = s
            break
    assert mu is not None
    assert mu.sharding.spec == P(None, "mp")
    new_state, metrics = round_fn(st, *shard_data(args))
    assert np.isfinite(float(metrics["loss_sum"]))
    assert int(new_state.round_idx) == 1
