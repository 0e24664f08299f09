"""``ops/linear_attention.py``: the chunked gated delta rule against the
token-by-token recurrence it must equal (float32, seeded inputs), and the
short causal convolution against its shifted multiply-adds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.linear_attention import (
    SUB, gated_delta_rule, gated_delta_rule_recurrent, short_causal_conv,
)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def inputs(L, H=2, d=16, a_log=0.0, seed=0):
    """q, k as the layer norms them, ``g = -exp(a_log) * softplus(.)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (L, H, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(a_log) * jax.nn.softplus(jax.random.normal(ks[3], (L, H, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (L, H)))
    return q, k, v, g, beta


WEAK, STRONG = np.log(0.05), np.log(16.0)


@pytest.mark.parametrize("a_log", [WEAK, STRONG], ids=["weak", "strong"])
@pytest.mark.parametrize("L, chunk", [(96, 32), (100, 32), (40, 64), (20, 8)],
                         ids=["divides", "ragged", "one_short_chunk",
                              "below_a_sub_block"])
def test_chunked_scan_equals_the_recurrence(L, chunk, a_log):
    args = inputs(L, a_log=a_log)
    want = gated_delta_rule_recurrent(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (L, 2, 16)
    assert rel(got, want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = [jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2, 3, 4))(
        *args) for f in (lambda *a: gated_delta_rule(*a, chunk=chunk),
                         gated_delta_rule_recurrent)]
    for name, ours, theirs in zip("q k v g beta".split(), *grads):
        assert np.isfinite(np.asarray(ours)).all(), name
        assert rel(ours, theirs) < 1e-5, name


def test_strong_decay_is_where_exp_of_minus_g_leaves_float32():
    """What the strong case is for: over one chunk a channel's cumulative log
    decay passes -88, so ``exp(-G)`` is inf in float32 and a form that
    factors ``exp(G_r - G_i)`` into ``exp(G_r) exp(-G_i)`` gives nan."""
    q, k, v, g, beta = inputs(96, a_log=STRONG)
    G = jnp.cumsum(g[:32], axis=0)
    assert float(G.min()) < -88 and not np.isfinite(np.asarray(jnp.exp(-G))).all()
    assert np.isfinite(np.asarray(gated_delta_rule(q, k, v, g, beta, 32))).all()


def test_a_state_carries_across_chunks():
    """Under weak decay a late output depends on an early token: the scan
    over chunks carries the state, it does not restart."""
    q, k, v, g, beta = inputs(64, a_log=WEAK)
    out = gated_delta_rule(q, k, v, g, beta, chunk=16)
    moved = gated_delta_rule(q, k, v.at[0].add(1.0), g, beta, chunk=16)
    assert rel(moved[-1], out[-1]) > 1e-3
    # and a later token moves no earlier output: it is causal
    later = gated_delta_rule(q, k, v.at[40].add(1.0), g, beta, chunk=16)
    assert float(jnp.abs(later[:40] - out[:40]).max()) == 0.0


def test_chunk_must_be_whole_sub_blocks():
    with pytest.raises(ValueError, match=f"multiple of {SUB}"):
        gated_delta_rule(*inputs(48), chunk=24)


def test_output_takes_the_dtype_of_v():
    q, k, v, g, beta = inputs(32)
    out = gated_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta, chunk=16)
    assert out.dtype == jnp.bfloat16
    assert rel(out.astype(jnp.float32),
               gated_delta_rule_recurrent(q, k, v, g, beta)) < 2e-2


@pytest.mark.parametrize("K", [4, 2])
def test_convolution_is_its_shifted_multiply_adds(K):
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (3, 12, 5)))
    taps = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (K, 5),
                                         minval=-0.5, maxval=0.5))
    want = np.zeros_like(u)
    for t in range(12):
        for j in range(K):
            if t - (K - 1) + j >= 0:  # zeros before the sequence starts
                want[:, t] += taps[j] * u[:, t - (K - 1) + j]
    np.testing.assert_allclose(short_causal_conv(jnp.asarray(u),
                                                 jnp.asarray(taps)),
                               want, rtol=1e-5, atol=1e-6)


def test_convolution_is_causal_at_the_sequence_start():
    u = jax.random.normal(jax.random.PRNGKey(0), (12, 5))
    taps = jax.random.uniform(jax.random.PRNGKey(1), (4, 5), minval=-0.5,
                              maxval=0.5)
    y = short_causal_conv(u, taps)
    # the first output sees the first token through the last tap only
    np.testing.assert_allclose(y[0], taps[3] * u[0], rtol=1e-6)
    # a token moves its own output and the three after it, nothing before
    moved = short_causal_conv(u.at[5].add(1.0), taps)
    changed = np.flatnonzero(np.abs(np.asarray(moved - y)).max(axis=1) > 0)
    assert changed.tolist() == [5, 6, 7, 8]
