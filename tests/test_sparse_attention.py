"""Attention over chosen keys: ``blockwise_attention(keep=)`` and the flash
kernels in interpret mode against an explicit softmax over the chosen pairs,
forward and the gradients of q, k and v; the q/k head norm of
``MultiHeadAttention`` against a norm by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.transformer import (
    MultiHeadAttention, _default_attn, lax_attention,
)
from fedml_tpu.ops import sparse_select as ss
from fedml_tpu.ops.flash_attention import flash_attention
from fedml_tpu.parallel.ring_attention import blockwise_attention

L, BLOCK, TOPK = 64, 16, 12


def explicit(q, k, v, keep):
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


@pytest.fixture(scope="module")
def choice():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    kI = jax.random.normal(ks[1], (L, 4))
    kI = kI.at[BLOCK:2 * BLOCK].set(0.0)  # a tile that no long row picks
    keep, tiles = ss.select_topk(
        jax.random.normal(ks[0], (L, 2, 4)), kI,
        jnp.abs(jax.random.normal(ks[2], (L, 2))), TOPK, block=BLOCK)
    assert not np.asarray(tiles)[3, 1] and np.asarray(tiles)[3, 0]
    return keep, tiles


FORMS = {
    "lax": lambda keep, tiles: lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, block_size=BLOCK, keep=keep),
    "policy_fallback": lambda keep, tiles: lambda q, k, v: lax_attention(
        q, k, v, True, keep=keep, tiles=tiles),
    "kernels": lambda keep, tiles: lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True,
        keep=keep, tiles=tiles),
    "kernels_vmapped": lambda keep, tiles: lambda q, k, v: jax.vmap(
        lambda q, k, v, keep, tiles: flash_attention(
            q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK,
            interpret=True, keep=keep, tiles=tiles))(
        q[None], k[None], v[None], keep[None], tiles[None])[0],
}
# (q heads, k/v heads, head size): 8 q heads to a k/v head (the kernels want
# whole 128-lane heads for that), and a head each
SHARING = [pytest.param(8, 1, 128, id="8_to_1"), pytest.param(8, 8, 16, id="1_to_1")]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("H, G, D", SHARING)
def test_forward_and_gradients_are_the_softmax_over_the_chosen_pairs(
        choice, form, H, G, D):
    keep, tiles = choice
    ks = jax.random.split(jax.random.PRNGKey(H + G), 4)
    q = jax.random.normal(ks[0], (L, H, D))
    k, v = (jax.random.normal(ks[i], (L, G, D)) for i in (1, 2))
    do = jax.random.normal(ks[3], (L, H, D))

    def both(fn):
        return fn(q, k, v), jax.grad(
            lambda q, k, v: (fn(q, k, v) * do).sum(), argnums=(0, 1, 2))(q, k, v)

    o, grads = both(FORMS[form](keep, tiles))
    want_o, want = both(lambda q, k, v: explicit(q, k, v, keep))
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_an_empty_tile_is_never_read(choice):
    """NaNs in the keys and values of a tile the table marks empty, for the
    queries that skip it, leave the kernels' output finite."""
    keep, tiles = choice
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(ks[i], (L, 8, 16)) for i in range(3))
    # rows of the last q block see tile (3, 1) as empty: cut the choice to them
    last = jnp.zeros_like(keep).at[3 * BLOCK:].set(keep[3 * BLOCK:])
    last = last.at[jnp.arange(3 * BLOCK), jnp.arange(3 * BLOCK)].set(1)
    table = ss.live_tiles(last, BLOCK)
    assert not np.asarray(table)[3, 1]
    o = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK,
                        interpret=True, keep=last, tiles=table)
    want = explicit(q, k, v, last)
    np.testing.assert_allclose(o, want, atol=2e-5)
    # the same with the table lying that the tile is live: same numbers, so
    # the table only skips work
    lying = table.at[3, 1].set(1)
    o2 = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK,
                         interpret=True, keep=last, tiles=lying)
    np.testing.assert_allclose(o2, want, atol=2e-5)


def test_a_choice_wants_its_table_the_causal_mask_and_no_window(choice):
    keep, tiles = choice
    q = jnp.zeros((L, 8, 16))
    with pytest.raises(ValueError, match="choice of keys"):
        flash_attention(q, q, q, causal=False, block_q=BLOCK, block_k=BLOCK,
                        interpret=True, keep=keep, tiles=tiles)
    with pytest.raises(ValueError, match="choice of keys"):
        flash_attention(q, q, q, causal=True, block_q=BLOCK, block_k=BLOCK,
                        interpret=True, keep=keep, tiles=tiles, window=8)
    with pytest.raises(ValueError, match="tile table"):
        flash_attention(q, q, q, causal=True, block_q=BLOCK, block_k=BLOCK,
                        interpret=True, keep=keep)
    with pytest.raises(ValueError, match="tile table"):  # another block's
        flash_attention(q, q, q, causal=True, block_q=2 * BLOCK,
                        block_k=2 * BLOCK, interpret=True, keep=keep,
                        tiles=tiles)


def test_the_policy_sends_a_choice_to_the_lax_path_off_the_tpu(choice):
    keep, tiles = choice
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(ks[i], (L, 4, 8)) for i in range(3))
    o = _default_attn(q, k, v, True, keep=keep, tiles=tiles)
    np.testing.assert_allclose(o, explicit(q, k, v, keep), atol=2e-5)


def test_q_and_k_heads_are_normed_before_the_attention_function():
    B, T, H, D, eps = 2, 6, 2, 4, 1e-6
    E = H * D
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, E))
    mha = MultiHeadAttention(H, attn_fn=lambda q, k, v, causal: q * k,
                             qk_norm=eps)
    gq, gk = (jax.random.normal(jax.random.PRNGKey(i), (D,)) for i in (1, 2))
    eye = jnp.eye(E)
    params = {"Dense_0": {"kernel": jnp.concatenate([eye] * 3, axis=1)},
              "Dense_1": {"kernel": eye},
              "q_norm": {"scale": gq}, "k_norm": {"scale": gk}}
    assert jax.tree_util.tree_map(jnp.shape, mha.init(
        jax.random.PRNGKey(3), x)["params"]) == jax.tree_util.tree_map(
        jnp.shape, params)
    heads = np.asarray(x).reshape(B, T, H, D)
    normed = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + eps)
    want = (normed * np.asarray(gq)) * (normed * np.asarray(gk))
    got = mha.apply({"params": params}, x)
    np.testing.assert_allclose(got, want.reshape(B, T, E), rtol=1e-5,
                               atol=1e-6)
    # off by default: no weight, no op
    plain = MultiHeadAttention(H, attn_fn=lambda q, k, v, causal: q * k)
    assert set(plain.init(jax.random.PRNGKey(3), x)["params"]) == {
        "Dense_0", "Dense_1"}
