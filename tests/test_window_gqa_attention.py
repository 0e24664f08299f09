"""A sliding window and grouped k/v heads in the two single-device attention
paths, the lax blockwise scan and the Pallas flash kernels (interpret mode),
against explicit masked softmax: forward and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.transformer import lax_attention
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.parallel.ring_attention import blockwise_attention


def explicit(q, k, v, window=None):
    """Causal softmax(q k^T / sqrt(d)) v with every score written out; k/v
    head g serves q heads g * rep .. (g + 1) * rep - 1."""
    L, H, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
    i, j = jnp.arange(L)[:, None], jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def qkv(L, H, G, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (L, H, D)),
            jax.random.normal(ks[1], (L, G, D)),
            jax.random.normal(ks[2], (L, G, D)))


def blockwise(block):
    return lambda q, k, v, window: blockwise_attention(
        q, k, v, causal=True, block_size=block, window=window)


def flash(block_q, block_k):
    return lambda q, k, v, window: flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        window=window, interpret=True)


# (attention, L, q heads, k/v heads, head size, window)
CASES = [
    pytest.param(blockwise(16), 64, 4, 4, 8, 16, id="lax_window_of_a_block"),
    pytest.param(blockwise(16), 64, 4, 4, 8, 21, id="lax_window_off_the_blocks"),
    pytest.param(blockwise(16), 64, 4, 1, 8, None, id="lax_grouped_4_to_1"),
    pytest.param(blockwise(16), 64, 4, 2, 8, 21, id="lax_grouped_and_window"),
    pytest.param(blockwise(24), 60, 6, 2, 8, 7, id="lax_ragged_length"),
    pytest.param(flash(32, 32), 128, 2, 2, 16, 32, id="flash_window_of_a_block"),
    pytest.param(flash(32, 32), 128, 2, 2, 16, 45, id="flash_window_off_the_blocks"),
    pytest.param(flash(32, 32), 128, 2, 2, 16, 7, id="flash_window_inside_a_block"),
    pytest.param(flash(32, 64), 128, 2, 2, 16, 45, id="flash_wide_kv_blocks"),
    pytest.param(flash(64, 32), 128, 2, 2, 16, 45, id="flash_wide_q_blocks"),
    pytest.param(flash(64, 64), 256, 4, 1, 128, None, id="flash_grouped_4_to_1"),
    pytest.param(flash(64, 64), 256, 4, 2, 128, 100, id="flash_grouped_and_window"),
    pytest.param(flash(32, 32), 128, 4, 4, 64, 45, id="flash_two_heads_a_block"),
]


@pytest.mark.parametrize("attn, L, H, G, D, window", CASES)
def test_forward_matches_explicit_masked_softmax(attn, L, H, G, D, window):
    q, k, v = qkv(L, H, G, D)
    np.testing.assert_allclose(attn(q, k, v, window),
                               explicit(q, k, v, window), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("attn, L, H, G, D, window", CASES)
def test_gradients_match_explicit_masked_softmax(attn, L, H, G, D, window):
    q, k, v = qkv(L, H, G, D, seed=1)
    probe = jax.random.normal(jax.random.PRNGKey(9), (L, H, D))
    grads = lambda f: jax.grad(  # noqa: E731
        lambda q, k, v: (f(q, k, v, window) * probe).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads(attn), grads(explicit)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("attn", [blockwise(16), flash(32, 32)],
                         ids=["lax", "flash"])
@pytest.mark.parametrize("window", [128, 1000])
def test_a_window_of_the_whole_length_is_the_causal_mask(attn, window):
    q, k, v = qkv(128, 2, 2, 16, seed=2)
    np.testing.assert_allclose(attn(q, k, v, window), attn(q, k, v, None),
                               rtol=1e-6, atol=1e-6)


def test_a_window_needs_the_causal_mask():
    q, k, v = qkv(64, 2, 2, 16)
    with pytest.raises(ValueError, match="causal"):
        blockwise_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8, block_q=32,
                        block_k=32, interpret=True)


def test_shared_kv_heads_need_one_head_a_column_block():
    q, k, v = qkv(128, 4, 2, 64)
    with pytest.raises(ValueError, match="k/v heads"):
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                        interpret=True)


def test_the_policys_fallback_takes_both():
    q, k, v = qkv(64, 4, 2, 8, seed=3)
    np.testing.assert_allclose(lax_attention(q, k, v, True, window=9),
                               explicit(q, k, v, 9), rtol=2e-5, atol=2e-5)
