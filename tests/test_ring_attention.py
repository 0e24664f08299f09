"""Ring attention / sequence parallelism: exactness vs dense attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.parallel.ring_attention import (blockwise_attention,
                                               dense_attention,
                                               ring_attention)


def _qkv(L=64, H=2, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(L, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(causal):
    q, k, v = _qkv()
    want = dense_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_size=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_ragged_blocks():
    q, k, v = _qkv(L=48)
    want = dense_attention(q, k, v, causal=True)
    got = blockwise_attention(q, k, v, causal=True, block_size=20)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense_on_8_devices(causal):
    L, H, D = 64, 2, 8
    q, k, v = _qkv(L=L, H=H, D=D, seed=1)
    want = dense_attention(q, k, v, causal=causal)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          block_size=8),
        mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
        out_specs=P("sp"), check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_ragged_shards(causal):
    """Shard length NOT divisible by block_size must still be exact
    (regression: unpadded ring partials double-counted clamped keys)."""
    L, H, D = 48, 2, 8   # 4 devices -> shard length 12, block_size 8
    q, k, v = _qkv(L=L, H=H, D=D, seed=5)
    want = dense_attention(q, k, v, causal=causal)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    fn = shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          block_size=8),
        mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
        out_specs=P("sp"), check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sequence_parallel_lm_matches_single_device():
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.parallel.sequence import (make_sequence_mesh,
                                             sequence_parallel_lm)

    mesh = make_sequence_mesh(8)
    module, init, apply = sequence_parallel_lm(
        mesh, vocab_size=50, embed_dim=32, num_heads=2, num_layers=2,
        max_len=256, block_size=8,
    )
    variables = init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 50, (2, 64)), jnp.int32
    )
    got = apply(variables, tokens)
    ref = TransformerLM(vocab_size=50, embed_dim=32, num_heads=2,
                        num_layers=2, max_len=256)
    want = ref.apply(variables, tokens, train=False)
    assert got.shape == (2, 64, 50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_transformer_trains_through_local_update():
    """The LM plugs into the same federated engine as every other model."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.core.types import FedDataset
    from fedml_tpu.models.transformer import transformer_lm

    rng = np.random.RandomState(0)
    seq = 16
    x = rng.randint(0, 30, (60, seq)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    ds = FedDataset(
        train_x=x[:48], train_y=y[:48], test_x=x[48:], test_y=y[48:],
        train_client_idx={c: np.arange(c * 16, (c + 1) * 16) for c in range(3)},
        test_client_idx=None, num_classes=30, name="lm-synth",
    )
    cfg = FedAvgConfig(num_clients=3, clients_per_round=3, comm_rounds=2,
                       epochs=1, batch_size=8, lr=0.1,
                       frequency_of_the_test=1)
    sim = FedAvgSimulation(
        transformer_lm(vocab_size=30, embed_dim=16, num_heads=2,
                       num_layers=1, seq_len=seq),
        ds, cfg,
    )
    hist = sim.run()
    assert np.isfinite(hist[-1]["train_loss"])
    assert "test_acc" in hist[-1]


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_dense(causal):
    """The flash-kernel ring path (per-step pallas attention + lse
    merging, interpret mode on CPU) must equal dense attention — and
    therefore the lax ring — exactly."""
    from fedml_tpu.parallel.ring_attention import ring_flash_attention

    L, H, D = 128, 2, 8  # 16 per shard -> no >=128 block; pass block=8
    q, k, v = _qkv(L=L, H=H, D=D, seed=3)
    want = dense_attention(q, k, v, causal=causal)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    fn = shard_map(
        functools.partial(ring_flash_attention, axis_name="sp",
                          causal=causal, block=8, interpret=True),
        mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
        out_specs=P("sp"), check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_gradients_match_lax_ring():
    """grad through the flash ring (custom VJP incl. the lse cotangent
    from the merge weights) must equal grad through the lax ring."""
    from fedml_tpu.parallel.ring_attention import ring_flash_attention

    L, H, D = 64, 2, 8
    q, k, v = _qkv(L=L, H=H, D=D, seed=5)
    cot = jnp.asarray(np.random.RandomState(9).randn(L, H, D).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))

    def make_loss(impl):
        fn = shard_map(
            impl, mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
            out_specs=P("sp"), check_vma=False,
        )
        return lambda q, k, v: (fn(q, k, v) * cot).sum()

    for causal in (False, True):
        g_flash = jax.grad(make_loss(functools.partial(
            ring_flash_attention, axis_name="sp", causal=causal, block=8,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        g_lax = jax.grad(make_loss(functools.partial(
            ring_attention, axis_name="sp", causal=causal, block_size=8)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_lax, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5,
                err_msg=f"{name} (causal={causal})",
            )


def test_sequence_parallel_lm_flash_impl():
    """The public attn_impl='flash' path (interpret on the CPU mesh)
    matches the default lax impl through a full LM forward; unknown impl
    names raise."""
    from fedml_tpu.parallel.sequence import (
        make_sequence_mesh, sequence_parallel_lm,
    )

    mesh = make_sequence_mesh(4)
    kwargs = dict(vocab_size=32, embed_dim=16, num_heads=2, num_layers=1,
                  max_len=64)
    _, init, apply_lax = sequence_parallel_lm(mesh, **kwargs, block_size=8)
    _, _, apply_flash = sequence_parallel_lm(
        mesh, **kwargs, attn_impl="flash", flash_block=8,
        flash_interpret=True,
    )
    vs = init(jax.random.PRNGKey(0), sample_len=16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 32)
    np.testing.assert_allclose(
        np.asarray(apply_flash(vs, toks)), np.asarray(apply_lax(vs, toks)),
        rtol=3e-4, atol=3e-4,
    )
    with pytest.raises(ValueError):
        sequence_parallel_lm(mesh, **kwargs, attn_impl="pallas")
    with pytest.raises(ValueError):  # block_size is a lax-path knob
        sequence_parallel_lm(mesh, **kwargs, attn_impl="flash", block_size=8)
