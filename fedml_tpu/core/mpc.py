"""Finite-field MPC primitives for secure aggregation (TurboAggregate).

Reference: ``fedml_api/distributed/turboaggregate/mpc_function.py`` —
``modular_inv:4``, ``gen_Lagrange_coeffs:38``, ``BGW_encoding:62``,
``BGW_decoding:91``, ``LCC_encoding*:110-193``, ``LCC_decoding:196``,
``Gen_Additive_SS:216``.

TPU-native design: coefficient generation (tiny, O(N²) scalar field
ops) stays on host in exact Python/numpy integers; the bulk
encode/decode — the O(N·m·d) share matmuls — run as jnp int64 ops
under jit.  With a prime p < 2³¹ every product of two residues is
< 2⁶², so an int64 multiply-accumulate with a mod after every term
never overflows; the accumulation is a ``lax.scan`` over the (small)
share dimension, vectorized over everything else.  Fixed-point
quantization maps float updates into the field with negatives as
p − |v| (two's-complement-style), so aggregation in the field equals
quantized aggregation in the reals — tested exactly.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Mersenne prime 2^31 - 1: largest field with overflow-free int64 modmul.
DEFAULT_PRIME = (1 << 31) - 1


# --- host-side exact scalar field math (coefficient generation) -------------

def modular_inv(a: int, p: int = DEFAULT_PRIME) -> int:
    """a⁻¹ mod p (Fermat; p prime). Exact Python ints — no overflow."""
    return pow(int(a) % p, p - 2, p)


def field_div(num: int, den: int, p: int = DEFAULT_PRIME) -> int:
    return (int(num) % p) * modular_inv(den, p) % p


def gen_lagrange_coeffs(
    alphas: Sequence[int], betas: Sequence[int], p: int = DEFAULT_PRIME
) -> np.ndarray:
    """U[i, j] = ∏_{o≠j} (αᵢ − β_o) / (β_j − β_o) mod p
    (reference ``gen_Lagrange_coeffs``, exact semantics, exact ints)."""
    alphas = [int(a) % p for a in alphas]
    betas = [int(b) % p for b in betas]
    U = np.zeros((len(alphas), len(betas)), dtype=np.int64)
    for i, a in enumerate(alphas):
        for j, bj in enumerate(betas):
            num, den = 1, 1
            for o in betas:
                if o != bj:
                    num = num * ((a - o) % p) % p
                    den = den * ((bj - o) % p) % p
            U[i, j] = field_div(num, den, p)
    return U


# --- device-side bulk share arithmetic --------------------------------------
#
# All jnp work below runs under ``jax.enable_x64(True)``: without the x64
# flag jnp silently truncates int64 → int32, which corrupts the field
# math.  The context is entered per public call; compiled int64 kernels
# are cached as usual.

@partial(jax.jit, static_argnames=("p",))
def _coeff_combine(U: jax.Array, X: jax.Array, p: int) -> jax.Array:
    def body(acc, uj_xj):
        u_j, x_j = uj_xj  # [N], [...]
        term = (u_j.reshape((-1,) + (1,) * x_j.ndim) * x_j[None]) % p
        return (acc + term) % p, None

    acc0 = jnp.zeros((U.shape[0],) + X.shape[1:], jnp.int64)
    acc, _ = jax.lax.scan(body, acc0, (U.T, X))
    return acc


def coeff_combine(U, X, p: int = DEFAULT_PRIME) -> jax.Array:
    """Y[i] = Σ_j U[i, j]·X[j] mod p, overflow-free.

    U: [N, S] residues; X: [S, ...] residues; Y: [N, ...].  A scan over
    the S share terms with a mod per step keeps every intermediate
    < 2⁶² + 2³¹ in int64.
    """
    with jax.enable_x64(True):
        U = jnp.asarray(np.asarray(U), jnp.int64) % p
        X = jnp.asarray(np.asarray(X), jnp.int64) % p
        return _coeff_combine(U, X, p)


def _lcc_grids(n: int, s: int, p: int) -> Tuple[np.ndarray, np.ndarray]:
    """(alphas[n], betas[s]) for LCC: betas are the interpolation points,
    alphas the share evaluation points.

    DELIBERATE DEFECT FIX vs the reference: ``LCC_encoding:122-125``
    centers both ranges, making β ⊂ α — a worker whose α equals β_j
    holds data chunk j in PLAINTEXT, so the T random chunks protect
    nothing for those workers.  LCC privacy requires the grids disjoint;
    here betas = 0..s−1 and alphas = s..s+n−1.
    """
    betas = np.arange(0, s)
    alphas = np.arange(s, s + n)
    return (
        np.mod(alphas, p).astype(np.int64),
        np.mod(betas, p).astype(np.int64),
    )


# --- BGW (Shamir) secret sharing --------------------------------------------

def bgw_encode(x: jax.Array, n: int, t: int, key: jax.Array,
               p: int = DEFAULT_PRIME) -> jax.Array:
    """Degree-t Shamir shares of ``x`` (field residues, any shape) for
    n parties at points α=1..n: share_i = Σ_k R_k·αᵢᵏ with R_0 = x
    (reference ``BGW_encoding:62-76``)."""
    with jax.enable_x64(True):
        x = jnp.asarray(np.asarray(x), jnp.int64) % p
        R = jax.random.randint(key, (t,) + x.shape, 0, p, dtype=jnp.int64)
        coeffs = jnp.concatenate([x[None], R], axis=0)  # [t+1, ...]
    alphas = np.arange(1, n + 1, dtype=np.int64) % p
    # Vandermonde α_i^k mod p, exact on host
    V = np.ones((n, t + 1), dtype=np.int64)
    for k in range(1, t + 1):
        V[:, k] = V[:, k - 1] * alphas % p
    return coeff_combine(V, coeffs, p)


def bgw_decode(shares: jax.Array, worker_idx: Sequence[int],
               p: int = DEFAULT_PRIME) -> jax.Array:
    """Reconstruct the secret from ≥ t+1 shares via Lagrange at 0
    (reference ``BGW_decoding:91-108``; ``worker_idx`` are 0-based)."""
    alphas = [(i + 1) % p for i in worker_idx]
    lam = gen_lagrange_coeffs([0], alphas, p)  # [1, R]
    return coeff_combine(lam, shares, p)[0]


# --- LCC (Lagrange coded computing) -----------------------------------------

def lcc_encode(x: jax.Array, n: int, k: int, t: int, key: jax.Array,
               p: int = DEFAULT_PRIME) -> jax.Array:
    """Split ``x`` (leading dim divisible by k) into k chunks + t random
    chunks, interpolate through β-points, evaluate at n α-points
    (reference ``LCC_encoding:110-135``).  Returns [n, m/k, ...]."""
    with jax.enable_x64(True):
        x = jnp.asarray(np.asarray(x), jnp.int64) % p
        m = x.shape[0]
        assert m % k == 0, f"leading dim {m} not divisible by K={k}"
        chunks = x.reshape((k, m // k) + x.shape[1:])
        if t > 0:
            R = jax.random.randint(
                key, (t,) + tuple(chunks.shape[1:]), 0, p, dtype=jnp.int64
            )
            chunks = jnp.concatenate([chunks, R], axis=0)
    alphas, betas = _lcc_grids(n, k + t, p)
    U = gen_lagrange_coeffs(alphas, betas, p)
    return coeff_combine(U, chunks, p)


def lcc_decode(shares: jax.Array, worker_idx: Sequence[int], n: int,
               num_chunks: int, p: int = DEFAULT_PRIME) -> jax.Array:
    """Recover ALL ``num_chunks`` = K+T interpolated chunk rows from the
    shares of ≥ num_chunks workers in ``worker_idx`` (reference
    ``LCC_decoding:196-212``).  The first K rows (after reshape) are the
    data chunks; callers slice off the trailing T random rows.  Pass the
    SAME K+T used at encode time — a smaller grid silently reconstructs
    garbage.  Returns [num_chunks·m', ...]."""
    alphas, betas = _lcc_grids(n, num_chunks, p)
    alpha_eval = [int(alphas[i]) for i in worker_idx]
    U = gen_lagrange_coeffs(betas, alpha_eval, p)
    out = coeff_combine(U, shares, p)
    return out.reshape((-1,) + tuple(out.shape[2:]))


# --- additive secret sharing -------------------------------------------------

def additive_shares(x: jax.Array, n: int, key: jax.Array,
                    p: int = DEFAULT_PRIME) -> jax.Array:
    """n shares summing to x mod p (reference ``Gen_Additive_SS:216-227``)."""
    with jax.enable_x64(True):
        x = jnp.asarray(np.asarray(x), jnp.int64) % p
        r = jax.random.randint(key, (n - 1,) + tuple(x.shape), 0, p, dtype=jnp.int64)
        last = (x - r.sum(axis=0) % p) % p
        return jnp.concatenate([r, last[None]], axis=0)


def field_sum(shares, p: int = DEFAULT_PRIME) -> jax.Array:
    """Σ over the leading axis, mod p (server-side share aggregation)."""
    with jax.enable_x64(True):
        s = jnp.asarray(np.asarray(shares), jnp.int64) % p

        def body(acc, row):
            return (acc + row) % p, None

        acc, _ = jax.lax.scan(body, jnp.zeros(s.shape[1:], jnp.int64), s)
        return acc


# --- fixed-point quantization (host boundary, exact float64) -----------------

def quantize(x, scale: float = 2.0 ** 16, p: int = DEFAULT_PRIME) -> np.ndarray:
    """Float → field: round(x·scale), negatives as p − |·|.  Values must
    satisfy |x|·scale·n_parties < p/2 for exact aggregate recovery."""
    v = np.round(np.asarray(x, np.float64) * scale).astype(np.int64)
    return np.mod(np.where(v < 0, v + p, v), p)


def dequantize(v, scale: float = 2.0 ** 16, p: int = DEFAULT_PRIME) -> np.ndarray:
    """Field → float, centered lift: residues > p/2 are negative."""
    v = np.mod(np.asarray(v, np.int64), p)
    signed = np.where(v > p // 2, v - p, v)
    return signed.astype(np.float64) / scale
