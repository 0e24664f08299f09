"""The state-space scan's Pallas kernels (``ops/ssd.py`` ``ssd_kernels``,
Pallas's interpreter off the TPU) against the definition a token at a time
(``ssd_recurrent``, float32) and the lax form (``ssd_chunked_lax``), values and
every gradient; and the dispatch by shape: a shape the kernels refuse keeps
the lax form, which lowers to the program of the commit before the kernels
came (``testdata/lowered_parent_pr47.json``)."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lowered_programs  # noqa: E402
from fedml_tpu.ops import ssd  # noqa: E402

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                      "lowered_parent_pr47.json")
NAMES = ("x", "dt", "A", "B", "C")


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def ssd_inputs(L, lowest, H, P, G, N, key=0):
    """Inputs whose log decays ``dt A`` run from about ``-1e-5`` a token (a
    decay near 1) down to ``lowest``."""
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    dt = jax.random.uniform(k[1], (L, H), minval=1e-3, maxval=1.0)
    A = -jnp.exp(jnp.linspace(math.log(0.01), math.log(-lowest), H))
    return (jax.random.normal(k[0], (L, H, P)), dt, A,
            jax.random.normal(k[3], (L, G, N)),
            jax.random.normal(k[4], (L, G, N)))


def value_and_grads(fn, args):
    """(``fn(*args)``, the gradients of a fixed weighting of it)."""
    y = fn(*args)
    weight = jnp.sin(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
    return y, jax.grad(lambda *a: (fn(*a) * weight).sum(),
                       argnums=range(5))(*args)


# (L, heads, head_dim, groups, lowest log decay): three chunks of 128 with a
# length the chunk does not divide, one group of two heads (a 128-row tile)
# and two groups of two (the B and C a head reads are its group's); decays
# down to exp(-10) a token
@pytest.mark.parametrize("L, H, P, G, lowest", [
    (300, 2, 64, 1, -3.0), (260, 4, 64, 2, -10.0)],
    ids=["3chunks_padded", "2groups_decay_exp-10"])
def test_the_kernels_are_the_recurrence_and_the_lax_form(L, H, P, G, lowest):
    args = ssd_inputs(L, lowest, H, P, G, 128)
    a = args[1] * args[2]
    assert float(a.min()) < 0.9 * lowest and float(a.max()) > -1e-4
    assert ssd.kernels_tile(128, H * P // G, P, 128)
    scan = functools.partial(ssd.ssd_chunked, chunk=128)
    ours, g_ours = value_and_grads(scan, args)
    theirs, g_theirs = value_and_grads(ssd.ssd_recurrent, args)
    lax, g_lax = value_and_grads(
        functools.partial(ssd.ssd_chunked_lax, chunk=128), args)
    assert ours.shape == theirs.shape == (L, H, P)
    np.testing.assert_array_equal(ours, ssd.ssd_kernels(*args, 128))
    assert rel(ours, theirs) < 1e-5 and rel(ours, lax) < 1e-5
    for name, g, t, m in zip(NAMES, g_ours, g_theirs, g_lax):
        assert bool(jnp.isfinite(g).all()), name
        assert rel(g, t) < 1e-4, name
        assert rel(g, m) < 1e-4, name


def test_the_kernels_keep_the_inputs_dtype():
    """bf16 ``x``, ``B`` and ``C``: ``y`` and the gradients of ``x``, ``B``
    and ``C`` come back in bf16, those of the float32 ``dt`` and ``A`` in
    float32, and ``y`` is the definition's on the same inputs to bf16's
    rounding (the CPU runs no bf16 dot of the lax form)."""
    x, dt, A, B, C = ssd_inputs(256, -1.0, 2, 64, 1, 128)
    low = [t.astype(jnp.bfloat16) for t in (x, B, C)]
    args = (low[0], dt, A, low[1], low[2])
    y, grads = value_and_grads(functools.partial(ssd.ssd_kernels, chunk=128),
                               args)
    assert y.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                         jnp.float32, jnp.bfloat16,
                                         jnp.bfloat16]
    exact = ssd.ssd_recurrent(*args)
    assert rel(y.astype(jnp.float32), exact) < 2e-2


# shapes the kernels refuse: a toy state, a group's channels off the 128-row
# tile, heads off the 16-row tile, a chunk off the 128-lane tile
@pytest.mark.parametrize("L, H, P, G, N, chunk", [
    (40, 4, 8, 2, 16, 16), (200, 3, 32, 1, 128, 128),
    (200, 16, 8, 1, 128, 128), (200, 2, 64, 1, 128, 64)],
    ids=["toy", "width96", "head8", "chunk64"])
def test_a_shape_the_kernels_refuse_keeps_the_lax_form(L, H, P, G, N, chunk):
    assert not ssd.kernels_tile(N, H * P // G, P, chunk)
    args = ssd_inputs(L, -3.0, H, P, G, N)
    scan = functools.partial(ssd.ssd_chunked, chunk=chunk)
    np.testing.assert_array_equal(scan(*args),
                                  ssd.ssd_chunked_lax(*args, chunk))
    assert "pallas" not in jax.jit(scan).lower(*args).as_text()
    with pytest.raises(ValueError, match="do not tile"):
        ssd.ssd_kernels(*args, chunk)


@pytest.mark.parametrize("program", lowered_programs.NEWEST)
def test_the_refused_shapes_lower_to_the_parents_program(program):
    """The ``nemotron_h`` toy (a 16-wide state: the lax form) and
    ``ssd_chunked`` alone at a toy shape lower to the programs of the
    commit before the kernels came."""
    with open(PINNED) as f:
        pinned = json.load(f)[program]
    assert lowered_programs.programs((program,))[program] == pinned
