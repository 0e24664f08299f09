"""``ops/linear_attention.py``: the chunked gated delta rule against the
token-by-token recurrence it must equal (float32, seeded inputs), as lax ops
and as the Pallas kernels (interpret mode here), and the short causal
convolution against its shifted multiply-adds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.linear_attention import (
    SUB, gated_delta_rule, gated_delta_rule_kernels, gated_delta_rule_lax,
    gated_delta_rule_recurrent, kernels_tile, short_causal_conv,
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


def grads(f, args, w):
    return jax.grad(lambda *a: (f(*a).astype(jnp.float32) * w).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("a_log", [WEAK, STRONG], ids=["weak", "strong"])
@pytest.mark.parametrize("L", [128, 100, 40],
                         ids=["divides", "ragged", "one_short_chunk"])
def test_the_kernels_equal_the_recurrence_and_the_lax_form(L, a_log):
    """Heads of 128 at chunk 64 take the kernels (through the one entry
    point: no argument chooses); ``o`` and the five gradients are the
    recurrence's and the lax form's to float32 rounding, finite under the
    strong decay too."""
    args = inputs(L, d=128, a_log=a_log)
    assert kernels_tile(128, 128, 64)
    assert "pallas_call" in str(jax.make_jaxpr(gated_delta_rule)(*args))
    want = gated_delta_rule_recurrent(*args)
    lax_form = gated_delta_rule_lax(*args, chunk=64)
    got = gated_delta_rule(*args, chunk=64)
    assert got.shape == want.shape == (L, 2, 128) and got.dtype == want.dtype
    assert rel(got, want) < 1e-5 and rel(got, lax_form) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    ours, theirs, middle = (grads(f, args, w) for f in (
        lambda *a: gated_delta_rule(*a, chunk=64), gated_delta_rule_recurrent,
        lambda *a: gated_delta_rule_lax(*a, chunk=64)))
    for name, a, b, c in zip("q k v g beta".split(), ours, theirs, middle):
        assert np.isfinite(np.asarray(a)).all(), name
        assert rel(a, b) < 1e-5 and rel(a, c) < 1e-5, name


def test_the_kernels_under_vmap_with_a_batch_of_two():
    """The layer's own call: ``vmap`` over the batch.  The state starts at
    zero for every sample (the second sample's first chunk sees none of the
    first's last)."""
    a, b = inputs(128, d=128, a_log=WEAK), inputs(128, d=128, seed=1)
    batch = tuple(jnp.stack(pair) for pair in zip(a, b))
    f = jax.vmap(lambda *t: gated_delta_rule(*t, chunk=64))
    got = f(*batch)
    for i, one in enumerate((a, b)):
        assert rel(got[i], gated_delta_rule_recurrent(*one)) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    ours = grads(f, batch, w)
    for i, one in enumerate((a, b)):
        theirs = grads(gated_delta_rule_recurrent, one, w[i])
        for name, x, y in zip("q k v g beta".split(), ours, theirs):
            assert rel(x[i], y) < 1e-5, name


def test_the_kernels_with_v_in_bfloat16_match_the_lax_form():
    """The cell's dtypes: ``v`` and ``o`` bf16, the state products on bf16
    operands added in float32, everything else float32, in both forms: they
    differ by bf16's rounding of ``o`` and of the products' operands."""
    q, k, v, g, beta = inputs(128, d=128, a_log=np.log(0.5))
    args = (q, k, v.astype(jnp.bfloat16), g, beta)
    got = gated_delta_rule(*args, chunk=64)
    want = gated_delta_rule_lax(*args, chunk=64)
    assert got.dtype == want.dtype == jnp.bfloat16
    f32 = jnp.float32
    assert rel(got.astype(f32), want.astype(f32)) < 1e-2
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    ours = grads(lambda *a: gated_delta_rule(*a, chunk=64), args, w)
    theirs = grads(lambda *a: gated_delta_rule_lax(*a, chunk=64), args, w)
    for name, a, b in zip("q k v g beta".split(), ours, theirs):
        assert a.dtype == b.dtype, name
        assert rel(a.astype(f32), b.astype(f32)) < 2e-2, name


@pytest.mark.parametrize("d, chunk, tiles", [
    (16, 32, False), (128, 8, False), (128, 64, True), (256, 16, True),
], ids=["head_of_16", "chunk_of_8", "the_cells", "head_of_256"])
def test_the_shape_alone_chooses_the_form(d, chunk, tiles):
    """No argument, flag or variable selects the path: heads of whole 128-lane
    tiles in chunks of whole 16-row tiles take the kernels, every other shape
    the lax form."""
    assert kernels_tile(d, d, chunk) is tiles
    text = str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=chunk))(*inputs(32, H=1, d=d)))
    assert ("pallas_call" in text) is tiles


def test_the_kernels_refuse_a_shape_they_do_not_tile():
    with pytest.raises(ValueError, match="do not tile"):
        gated_delta_rule_kernels(*inputs(32, d=16), chunk=32)


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
