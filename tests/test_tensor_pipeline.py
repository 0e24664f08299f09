"""Tensor- and pipeline-parallel tests on the faked 8-device CPU mesh.

Correctness oracle in both cases: the sharded program must equal the
single-device serial program (cf. the reference's FL==centralized
equivalence strategy, SURVEY.md §4.3, applied to parallelism)."""

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from fedml_tpu.algorithms.fedavg import ServerState
from fedml_tpu.core.client import make_client_optimizer, make_local_update
from fedml_tpu.models.transformer import lax_attention, transformer_lm
from fedml_tpu.parallel.mesh import make_dp_mp_mesh
from fedml_tpu.parallel.partition import (
    FEDLLM_RULES,
    make_rule_round_fn,
    shard_by_rules,
)
from fedml_tpu.parallel.pipeline import (
    make_gpipe,
    make_pp_mesh,
    serial_reference,
    shard_stage_params,
    stack_stage_params,
)


def _tp_lm(num_layers):
    """A (1, 4) mesh — tensor parallelism alone is the rule engine with
    no cohort axis — and the LM whose heads GSPMD shards over ``mp``
    (the lax attention: a pallas_call has no partitioning rule)."""
    bundle = transformer_lm(
        vocab_size=64, embed_dim=32, num_heads=4, num_layers=num_layers,
        seq_len=16, attn_fn=lax_attention,
    )
    return make_dp_mp_mesh(1, 4), bundle


def test_tensor_parallel_forward_matches_single_device():
    mesh, bundle = _tp_lm(num_layers=2)
    variables = bundle.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    ref = bundle.apply_eval(variables, tokens)
    sharded_vars, _ = shard_by_rules(mesh, variables, FEDLLM_RULES)
    out = jax.jit(bundle.apply_eval)(sharded_vars, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_tp_params_actually_sharded():
    mesh, bundle = _tp_lm(num_layers=1)
    variables, _ = shard_by_rules(
        mesh, bundle.init(jax.random.PRNGKey(0)), FEDLLM_RULES
    )
    qkv = variables["params"]["Block_0"]["MultiHeadAttention_0"]["Dense_0"]["kernel"]
    mlp_down = variables["params"]["Block_0"]["Dense_1"]["kernel"]
    wte = variables["params"]["wte"]["embedding"]
    assert qkv.sharding.spec == P(None, "mp")
    assert mlp_down.sharding.spec == P("mp", None)
    # the table shards the vocabulary too (the tied head's matmul is
    # row-parallel for free)
    assert wte.sharding.spec == P("mp", None)
    assert len(qkv.sharding.device_set) == 4
    # each device holds a quarter of the column-parallel kernel
    shard_shapes = {s.data.shape for s in qkv.addressable_shards}
    assert shard_shapes == {(32, 96 // 4)}
    assert {s.data.shape for s in wte.addressable_shards} == {(64 // 4, 32)}


def test_tp_train_step_learns_and_keeps_sharding():
    """A tensor-parallel training step is a round on a (1, n) mesh: one
    client, one SGD step a round."""
    mesh, bundle = _tp_lm(num_layers=1)
    local_update = make_local_update(
        bundle, make_client_optimizer("sgd", 0.5), epochs=1
    )
    key = jax.random.PRNGKey(0)
    state = ServerState(
        variables=bundle.init(key), opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=key,
    )
    round_fn, shard_state, shard_data = make_rule_round_fn(
        mesh, local_update, state.variables, FEDLLM_RULES
    )
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    )
    # [K=1, steps=1, batch=4, L]: per-position targets and mask
    x = tokens[None, None]
    args = shard_data((
        x, np.roll(x, -1, axis=-1), np.ones((1, 1, 4), np.float32),
        np.full((1,), 4.0, np.float32), np.ones((1,), np.float32),
        np.zeros((1,), np.int32),
    ))
    state = shard_state(state)
    losses = []
    for _ in range(5):
        state, m = round_fn(state, *args)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    qkv = state.variables["params"]["Block_0"]["MultiHeadAttention_0"]["Dense_0"]["kernel"]
    assert qkv.sharding.spec == P(None, "mp")


def _mlp_stage(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"] + x  # residual keeps scale


def _random_stages(key, num_stages, feat, hidden):
    stages = []
    for s in range(num_stages):
        k1, k2, key = jax.random.split(jax.random.fold_in(key, s), 3)
        stages.append({
            "w1": jax.random.normal(k1, (feat, hidden)) * 0.3,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, feat)) * 0.3,
            "b2": jnp.zeros((feat,)),
        })
    return stages


def test_gpipe_matches_serial():
    mesh = make_pp_mesh(4)
    stacked = stack_stage_params(_random_stages(jax.random.PRNGKey(0), 4, 8, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 8))  # [M, B, F]
    apply = make_gpipe(mesh, _mlp_stage)
    out = apply(shard_stage_params(mesh, stacked), x)
    ref = serial_reference(_mlp_stage, stacked, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gpipe_backward_matches_serial():
    """ppermute transposes correctly: per-stage parameter gradients from
    the pipelined program equal the serial program's."""
    mesh = make_pp_mesh(4)
    stacked = stack_stage_params(_random_stages(jax.random.PRNGKey(2), 4, 8, 16))
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 8))
    target = jax.random.normal(jax.random.PRNGKey(4), (5, 2, 8))
    apply = make_gpipe(mesh, _mlp_stage)

    def pipe_loss(p):
        return jnp.mean((apply(p, x) - target) ** 2)

    def serial_loss(p):
        return jnp.mean((serial_reference(_mlp_stage, p, x) - target) ** 2)

    g_pipe = jax.grad(pipe_loss)(shard_stage_params(mesh, stacked))
    g_ref = jax.grad(serial_loss)(stacked)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g_pipe,
        g_ref,
    )


def test_moe_matches_serial_when_no_drops():
    """EP: all_to_all-dispatched MoE equals the serial top-1 oracle when
    capacity is large enough that no token is dropped."""
    from fedml_tpu.parallel.expert import (
        init_moe_params, make_ep_mesh, make_moe_ffn, moe_reference,
        shard_moe_params,
    )
    mesh = make_ep_mesh(4)
    params = init_moe_params(jax.random.PRNGKey(0), 4, d_model=8, d_hidden=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    apply = make_moe_ffn(mesh, capacity=8)  # 8 local tokens/device = no drops
    out = apply(shard_moe_params(mesh, params), x)
    ref = moe_reference(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_moe_capacity_drops_zero_out():
    """Tokens past an expert's queue capacity contribute zeros (the
    residual path), never garbage."""
    from fedml_tpu.parallel.expert import (
        init_moe_params, make_ep_mesh, make_moe_ffn, shard_moe_params,
    )
    mesh = make_ep_mesh(4)
    params = init_moe_params(jax.random.PRNGKey(0), 4, d_model=8, d_hidden=16)
    # steer every token to expert 0: positive inputs + a gate whose
    # column 0 is all-ones×50 → logit 0 dominates; with capacity 1 only
    # the first local token per device survives
    params["gate"] = jnp.zeros((8, 4)).at[:, 0].set(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (32, 8))) + 0.1
    out = np.asarray(make_moe_ffn(mesh, capacity=1)(shard_moe_params(mesh, params), x))
    nonzero_rows = (np.abs(out) > 1e-9).any(axis=1)
    assert nonzero_rows.sum() == 4  # one surviving token per device
    # the survivors are each device's first local token (local t=8)
    assert set(np.where(nonzero_rows)[0]) == {0, 8, 16, 24}
