"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.flash_attention import (
    flash_attention, flash_attention_with_lse, flash_attn_fn,
)
from fedml_tpu.parallel.ring_attention import dense_attention


def _qkv(L=64, H=2, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(L, H, D).astype(np.float32))
    return mk(), mk(), mk()


# (L, H, D, block_q, block_k).  Head size 64 runs two heads in one
# 128-lane block; blocks smaller than L make the kernels' own loops walk
# wholly visible, diagonal and (causal) skipped blocks, and block_q !=
# block_k moves the diagonal off the block corners.
SHAPES = [
    pytest.param((64, 2, 16, 16, 16), id="d16"),
    pytest.param((256, 2, 64, 128, 128), id="d64_two_heads_a_block"),
    pytest.param((256, 4, 64, 64, 128), id="d64_wide_kv_blocks"),
    pytest.param((256, 4, 64, 128, 64), id="d64_wide_q_blocks"),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal, shape):
    L, H, D, block_q, block_k = shape
    q, k, v = _qkv(L, H, D)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_multi_qkv_blocks_carry_state():
    # several q blocks × several kv blocks exercises the scratch carry
    q, k, v = _qkv(L=96, H=1, D=8, seed=3)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_rejects_ragged():
    q, k, v = _qkv(L=60)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)


def test_flash_attn_fn_plugs_into_transformer():
    from fedml_tpu.models.transformer import TransformerLM

    m = TransformerLM(vocab_size=40, embed_dim=32, num_heads=2,
                      num_layers=1, max_len=128,
                      attn_fn=flash_attn_fn(block_q=16, block_k=16,
                                            interpret=True))
    ref = TransformerLM(vocab_size=40, embed_dim=32, num_heads=2,
                        num_layers=1, max_len=128)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 40, (2, 32)))
    variables = ref.init({"params": jax.random.PRNGKey(0)}, tokens)
    want = ref.apply(variables, tokens)
    got = m.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def _dense_with_lse(q, k, v, causal):
    """(o, lse [H, L]) of dense softmax attention."""
    s = jnp.einsum("qhd,khd->hqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[1:], bool)), s, -jnp.inf)
    return dense_attention(q, k, v, causal=causal), \
        jax.scipy.special.logsumexp(s, axis=-1)


@pytest.mark.parametrize("shape", [
    pytest.param((32, 2, 8, 8, 8), id="d8"),
    *SHAPES[1:],
    pytest.param((256, 2, 128, 64, 128), id="d128_one_head_a_block"),
])
@pytest.mark.parametrize("lse_cotangent", [False, True],
                         ids=["o", "o_and_lse"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal, lse_cotangent, shape):
    """The backward kernel must produce the same dq/dk/dv as
    differentiating dense softmax attention: through ``o`` alone, and
    through ``o`` and ``lse`` (what the ring merge does) with a cotangent
    of ``lse`` that is not zero.  Several kv blocks a head block walk the
    dq accumulator the kernel carries from one kv step to the next."""
    L, H, D, block_q, block_k = shape
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(k1, (L, H, D), jnp.float32)
    k = jax.random.normal(k2, (L, H, D), jnp.float32)
    v = jax.random.normal(k3, (L, H, D), jnp.float32)
    cot = jax.random.normal(k4, (L, H, D), jnp.float32)
    cot_lse = jax.random.normal(k5, (H, L), jnp.float32) * lse_cotangent

    def loss(attn):
        def f(q, k, v):
            out, lse = attn(q, k, v)
            return (out * cot).sum() + (lse * cot_lse).sum()
        return f

    gf = jax.grad(loss(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal, block_q, block_k, True)), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: _dense_with_lse(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=f"{name} diverged from dense-attention gradient",
        )


def test_default_attn_policy(monkeypatch):
    """What ``_default_attn`` sends to the kernels: nothing on the CPU;
    on a TPU 16-bit inputs at any length a block divides, float32 only
    from L = 2048 (below it the lax path, which is the benchmark
    reference's attention), ragged lengths never."""
    from jax.experimental import pallas as pl

    from fedml_tpu.models.transformer import _default_attn
    from fedml_tpu.ops.flash_attention import head_group, pick_block

    traced = []  # as chip_smoke.Watch records them
    real = pl.pallas_call

    def recording_pallas_call(kernel, *args, **kwargs):
        traced.append(kernel.func.__name__)
        return real(kernel, *args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", recording_pallas_call)

    def kernels(L, H, D, dtype):
        del traced[:]
        x = jax.ShapeDtypeStruct((L, H, D), dtype)
        jax.eval_shape(lambda q, k, v: jax.grad(
            lambda q: _default_attn(q, k, v, True).astype(jnp.float32).sum()
        )(q), x, x, x)
        return list(traced)

    assert kernels(1024, 20, 64, jnp.bfloat16) == []      # the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fused = ["_fwd_kernel", "_bwd_kernel"]
    assert kernels(1024, 20, 64, jnp.bfloat16) == fused
    assert kernels(1024, 20, 64, jnp.float32) == []
    assert kernels(2048, 10, 128, jnp.float32) == fused
    assert kernels(1000, 20, 64, jnp.bfloat16) == []      # ragged
    assert kernels(1024, 25, 64, jnp.bfloat16) == []      # 1600 lanes: no
    # whole number of 128-lane blocks of two heads

    for L, D in ((1024, 64), (2048, 128), (8192, 128)):
        assert pick_block(L, D) and L % pick_block(L, D) == 0
    assert pick_block(1000, 64) == 0
    assert head_group(20, 64) == 2 and head_group(10, 128) == 1
    assert head_group(25, 64) == 0 and head_group(12, 96) == 0


def test_flash_trains_through_local_update():
    """End-to-end: a transformer local update differentiating THROUGH the
    flash kernel (interpret mode on CPU) runs and produces finite loss,
    matching the blockwise-attention update."""
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.transformer import transformer_lm
    from fedml_tpu.ops.flash_attention import flash_attn_fn
    from fedml_tpu.parallel.ring_attention import blockwise_attention

    L, V = 16, 32
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 4, L), 0, V)
    y = jnp.roll(x, -1, -1)
    m = jnp.ones((2, 4), jnp.float32)
    opt = make_client_optimizer("sgd", 0.1)

    results = []
    for attn in (
        flash_attn_fn(block_q=8, block_k=8, interpret=True),
        lambda q, k, v, causal: blockwise_attention(q, k, v, causal=causal,
                                                    block_size=8),
    ):
        b = transformer_lm(vocab_size=V, embed_dim=16, num_heads=2,
                           num_layers=1, seq_len=L, attn_fn=attn)
        lu = make_local_update(b, opt, epochs=1)
        new_vars, met = jax.jit(lu.fn)(
            b.init(jax.random.PRNGKey(0)), x, y, m, jax.random.PRNGKey(1)
        )
        results.append((new_vars, float(met["loss_sum"])))
    (vf, lf), (vb, lb) = results
    assert np.isfinite(lf)
    np.testing.assert_allclose(lf, lb, rtol=1e-4)
    for a, b_ in zip(jax.tree_util.tree_leaves(vf),
                     jax.tree_util.tree_leaves(vb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


# (L, H, q/k head size, v head size, block): the latent-attention layer's
# 192 / 128 (two heads a column block: 384 lanes of q and k, 256 of v), and
# a toy pair the policy sends to no chip (one block of every head)
UNEQUAL = [
    pytest.param((256, 4, 192, 128, 128), id="d192_v128_two_heads_a_block"),
    pytest.param((64, 2, 12, 8, 32), id="d12_v8_every_head_one_block"),
]


@pytest.mark.parametrize("shape", UNEQUAL)
def test_flash_takes_a_v_head_size_of_its_own(shape):
    """Forward and the three gradients against explicit scores and softmax,
    q and k of one head size and v of another."""
    L, H, D, Dv, block = shape
    rng = np.random.RandomState(5)
    q, k = (jnp.asarray(rng.randn(L, H, D).astype(np.float32)) for _ in "qk")
    v, w = (jnp.asarray(rng.randn(L, H, Dv).astype(np.float32)) for _ in "vw")

    def explicit(q, k, v):
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, interpret=True)

    assert flash(q, k, v).shape == (L, H, Dv)
    np.testing.assert_allclose(flash(q, k, v), explicit(q, k, v),
                               rtol=2e-5, atol=2e-5)
    ours, theirs = (jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (flash, explicit))
    for name, a, b in zip("qkv", ours, theirs):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


def test_policy_sends_unequal_head_sizes_to_the_kernels(monkeypatch):
    """From what the call can see: 4 heads of 192 / 128 lie two a column
    block; 3 heads do not (no whole number of blocks) and take the lax scan."""
    from jax.experimental import pallas as pl

    from fedml_tpu.models.transformer import _default_attn
    from fedml_tpu.ops.flash_attention import head_group

    assert head_group(4, 192, 128) == 2 and head_group(32, 192, 128) == 2
    assert head_group(3, 192, 128) == 0 and head_group(4, 128, 256) == 1
    assert head_group(4, 128, 128) == head_group(4, 128) == 1
    traced = []
    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda kernel, *a, **kw: (
        traced.append(kernel.func.__name__), real(kernel, *a, **kw))[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def kernels(H):
        del traced[:]
        spec = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
            (1024, H, d), jnp.bfloat16)
        out = jax.eval_shape(lambda q, k, v: _default_attn(q, k, v, True),
                             spec(192), spec(192), spec(128))
        assert out.shape == (1024, H, 128)
        return list(traced)

    assert kernels(4) == ["_fwd_kernel"]
    assert kernels(3) == []
