"""Pallas TPU implicit-GEMM conv kernel for narrow-channel 3×3 stages.

The north-star workload (ResNet-56, ``models/resnet.py``) runs its 3×3
convs at channel widths 16/32/64: a 128-lane MXU executes them at
12.5/25/50% output-lane occupancy, and round 5 measured every classic
dense retiling (s2d2/s2d3/pad32) as a net loss — any transform that
widens lanes also inflates K or shrinks M (PROFILE.md round-5 table).
This kernel attacks the one axis those transforms could not reach: it
formulates the conv as an **implicit GEMM**

    patches(x)  : [M = N·Ho·Wo, K = 9·Cin]   (gathered in VMEM)
    kernel      : [K, Cout]
    out         : [M, Cout] = patches @ kernel

so the contraction depth grows 9× (Cin=16 → K=144: two K-tiles instead
of one eighth of one) and the huge M axis — which XLA's conv tiling
fragments across the spatial dims — is packed densely into MXU rows.
The lane-starved Cout axis is untouched (that is the structural part of
the ceiling); the bet is purely on M/K packing efficiency.

Fusion: an optional per-channel affine + ReLU epilogue
(``mul``/``add``/``relu``) and optional per-channel moment outputs
(sum, sum-of-squares of the emitted activations).  The moments path is
what the train loop uses: BatchNorm's batch statistics come out of the
conv kernel itself instead of a separate full-tensor ``reduce_sum``
re-read of the activations from HBM — the 7.2% ``reduce_sum`` share in
PROFILE.md's round-2 accounting is partly that re-read.

Differentiability: ``conv3x3`` / ``conv3x3_moments`` carry a
``jax.custom_vjp``.  The backward is the first-cut XLA-conv form the
issue allows — dgrad/wgrad are emitted by XLA's own conv-transpose
rules (which lower to GEMMs on TPU) via a ``jax.vjp`` whose unused
primal is dead-code-eliminated under jit; the moments cotangents fold
into the output cotangent analytically (d sum → broadcast, d sumsq →
2·y) before the transpose convs run.  A Pallas dgrad/wgrad pair is the
follow-up once the forward has a measured win.

On a TPU the kernel is compiled by Mosaic and nothing runs in its place
if Mosaic refuses it (``chip_smoke.py`` leg ``conv_mxu`` compiles every
shape ResNet-56 uses and compares with ``_xla_conv3x3``).

CPU/testing: ``interpret=None`` auto-selects Pallas interpret mode off
the TPU backend (the ``ops/flash_attention.py`` precedent), so the full
parity suite (``tests/test_conv_mxu.py``) runs in tier-1 on CPU and the
faked-mesh tests keep passing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DN = ("NHWC", "HWIO", "NHWC")

# target GEMM-row count per kernel invocation: at least 4 MXU row-tiles
# of 128 so the systolic array's fill/drain amortizes; stage 3's 8×8
# maps pack 8 images per program to reach it
_TARGET_M = 512


def _pick_block_n(n: int, out_hw: int) -> int:
    """Images per kernel invocation: the largest divisor of ``n`` whose
    patch matrix stays modest while M = block_n·Ho·Wo reaches
    ``_TARGET_M`` (single-image for the big stage-1 maps)."""
    bn = 1
    while bn * out_hw < _TARGET_M and (n % (bn * 2) == 0):
        bn *= 2
    return bn


def _conv_kernel(x_ref, w_ref, mul_ref, add_ref, *out_refs, stride: int,
                 relu: bool, moments: bool):
    """One grid step: gather 9 shifted taps of a padded image block into
    the [M, 9·Cin] patch scratch, run ONE MXU matmul against the
    [9·Cin, Cout] kernel, apply the affine(+ReLU) epilogue, and emit the
    block's per-channel moment partials.

    The tap gather is a unit-stride ``lax.slice`` of one PHASE of the
    VMEM-resident padded block.  Mosaic has no strided slice of a value
    (``vector.extract_strided_slice``: strides confined to [1, 2)), so
    the wrapper splits the padded image into its stride² phases —
    ``x_ref[:, py·s+px][a, b] = x_pad[s·a+py, s·b+px]`` — and tap
    (ty, tx) reads phase (ty % s, tx % s) at offset (ty // s, tx // s).
    Stride 1 is the one-phase case; stride 2 reads the even-center
    windows of the baseline's explicit-padding convention (out[i] ←
    padded rows 2i..2i+2), so the stride-2 stage transitions compute
    the identical function."""
    if moments:
        o_ref, sum_ref, sq_ref, patch = out_refs
    else:
        o_ref, patch = out_refs
    bn, ho, wo, co = o_ref.shape
    ci = x_ref.shape[-1]
    phases = [x_ref[:, p] for p in range(stride * stride)]
    for t in range(9):
        ty, tx = divmod(t, 3)
        oy, ox = ty // stride, tx // stride
        tap = jax.lax.slice(
            phases[(ty % stride) * stride + tx % stride],
            (0, oy, ox, 0), (bn, oy + ho, ox + wo, ci),
        )                                           # (bn, Ho, Wo, Ci)
        patch[:, t * ci:(t + 1) * ci] = tap.reshape(bn * ho * wo, ci)
    acc = jax.lax.dot_general(
        patch[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (M, Co) fp32
    y = acc * mul_ref[:] + add_ref[:]
    if relu:
        y = jnp.maximum(y, 0.0)
    yc = y.astype(o_ref.dtype)
    o_ref[:] = yc.reshape(bn, ho, wo, co)
    if moments:
        # moments of the EMITTED activations (post-cast, post-epilogue):
        # exactly the values train-mode BatchNorm reduces over, so the
        # fp32 stats match the baseline's astype(float32) reduction
        yf = yc.astype(jnp.float32)
        sum_ref[0] = jnp.sum(yf, axis=0, keepdims=True)
        sq_ref[0] = jnp.sum(yf * yf, axis=0, keepdims=True)


def conv3x3_mxu(x, w, *, stride: int = 1, mul=None, add=None,
                relu: bool = False, moments: bool = False,
                block_n: int | None = None, interpret: bool | None = None):
    """Raw (non-differentiable) implicit-GEMM 3×3 SAME conv.

    x [N, H, W, Cin] · w [3, 3, Cin, Cout], explicit padding 1 each
    side, stride ∈ {1, 2} — the baseline ``_XConv`` convention
    (even-center windows at stride 2).  ``mul``/``add`` [Cout] fuse a
    per-channel fp32 affine into the epilogue (BN-affine in eval form),
    ``relu`` fuses the activation, ``moments=True`` additionally
    returns per-channel (sum, sumsq) of the emitted output.

    Returns ``out`` or ``(out, sum, sumsq)``.
    """
    n, h, wdim, ci = x.shape
    if w.shape[:2] != (3, 3) or w.shape[2] != ci:
        raise ValueError(f"need a [3,3,{ci},Co] kernel, got {w.shape}")
    if stride not in (1, 2):
        raise ValueError(f"stride must be 1 or 2, got {stride}")
    if h % stride or wdim % stride:
        raise ValueError(f"spatial dims {(h, wdim)} must divide stride")
    co = w.shape[3]
    ho, wo = h // stride, wdim // stride
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_n is None:
        block_n = _pick_block_n(n, ho * wo)
    if n % block_n:
        raise ValueError(f"batch {n} must divide block_n {block_n}")
    m = block_n * ho * wo

    x_pad = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # (N, H+2, W+2, Ci) → (N, s², (H+2)/s, (W+2)/s, Ci): phase py·s+px
    # holds rows py, py+s, … and columns px, px+s, … (see _conv_kernel)
    hp, wp = (h + 2) // stride, (wdim + 2) // stride
    x_ph = x_pad.reshape(n, hp, stride, wp, stride, ci).transpose(
        0, 2, 4, 1, 3, 5).reshape(n, stride * stride, hp, wp, ci)
    # (3, 3, Ci, Co) → (9·Ci, Co): row t·Ci+c is tap (ty, tx)=divmod(t,3),
    # input channel c — the exact column order the tap gather writes
    w2 = w.astype(x.dtype).reshape(9 * ci, co)
    mul_arr = (jnp.ones((1, co), jnp.float32) if mul is None
               else jnp.asarray(mul, jnp.float32).reshape(1, co))
    add_arr = (jnp.zeros((1, co), jnp.float32) if add is None
               else jnp.asarray(add, jnp.float32).reshape(1, co))

    grid = (n // block_n,)
    kernel = functools.partial(
        _conv_kernel, stride=stride, relu=relu, moments=moments
    )
    out_shape = [jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype)]
    out_specs = [pl.BlockSpec((block_n, ho, wo, co),
                              lambda g: (g, 0, 0, 0))]
    if moments:
        # one (1, Co) row per grid step, as a (G, 1, Co) array: a block's
        # last two dims must be whole tiles or the array's own, and
        # (1, Co) of a (G, Co) array is neither
        out_shape += [jax.ShapeDtypeStruct((grid[0], 1, co), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, 1, co), lambda g: (g, 0, 0))] * 2
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, stride * stride, hp, wp, ci),
                         lambda g: (g, 0, 0, 0, 0)),
            pl.BlockSpec((9 * ci, co), lambda g: (0, 0)),
            pl.BlockSpec((1, co), lambda g: (0, 0)),
            pl.BlockSpec((1, co), lambda g: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((m, 9 * ci), x.dtype)],
        interpret=interpret,
        **kwargs,
    )(x_ph, w2, mul_arr, add_arr)
    if moments:
        y, s, sq = out
        return y, s.sum(axis=(0, 1)), sq.sum(axis=(0, 1))
    return out[0]


def _xla_conv3x3(x, w, stride: int):
    """The XLA conv computing the identical function — the parity
    reference AND the source of the first-cut backward (its transpose
    rules emit the dgrad/wgrad GEMMs)."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(1, 1), (1, 1)], dimension_numbers=_DN
    )


def _conv_vjp(x, w, stride, dy):
    """dgrad/wgrad via XLA's conv-transpose rules.  The vjp's unused
    primal conv is dead code under jit, so this costs exactly the two
    transpose convs."""
    _, vjp = jax.vjp(lambda xx, ww: _xla_conv3x3(xx, ww, stride), x, w)
    return vjp(dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv3x3(x, w, stride: int = 1, block_n: int | None = None,
            interpret: bool | None = None):
    """Differentiable implicit-GEMM 3×3 conv (Pallas forward, XLA-GEMM
    backward).  Drop-in for the baseline ``lax.conv_general_dilated``
    call in ``models/resnet_tpu._XConv`` (explicit padding 1, NHWC)."""
    return conv3x3_mxu(x, w, stride=stride, block_n=block_n,
                       interpret=interpret)


def _conv3x3_fwd(x, w, stride, block_n, interpret):
    return conv3x3(x, w, stride, block_n, interpret), (x, w)


def _conv3x3_bwd(stride, block_n, interpret, res, dy):
    del block_n, interpret
    x, w = res
    return _conv_vjp(x, w, stride, dy)


conv3x3.defvjp(_conv3x3_fwd, _conv3x3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv3x3_moments(x, w, stride: int = 1, block_n: int | None = None,
                    interpret: bool | None = None):
    """``conv3x3`` fused with per-channel moment emission: returns
    ``(out, sum, sumsq)`` where sum/sumsq reduce the emitted output
    over every (image, row, col) position in fp32 — the quantities
    train-mode BatchNorm needs, produced without a second full-tensor
    HBM read.  Differentiable in all three outputs (the BN mean/var
    gradient flows through the moment cotangents)."""
    return conv3x3_mxu(x, w, stride=stride, moments=True, block_n=block_n,
                       interpret=interpret)


def _conv3x3_moments_fwd(x, w, stride, block_n, interpret):
    y, s, sq = conv3x3_moments(x, w, stride, block_n, interpret)
    return (y, s, sq), (x, w, y)


def _conv3x3_moments_bwd(stride, block_n, interpret, res, g):
    del block_n, interpret
    x, w, y = res
    dy, ds, dsq = g
    # fold the moment cotangents into the output cotangent analytically:
    #   sum_c  = Σ_m y[m, c]   → d y += ds[c]  (broadcast)
    #   sumsq_c = Σ_m y[m, c]² → d y += 2·y·dsq[c]
    # accumulated in fp32 then cast at the same point the baseline's
    # astype(float32) BN-stat chain casts its cotangent
    dy_eff = (dy.astype(jnp.float32)
              + ds[None, None, None, :]
              + 2.0 * y.astype(jnp.float32) * dsq[None, None, None, :]
              ).astype(y.dtype)
    return _conv_vjp(x, w, stride, dy_eff)


conv3x3_moments.defvjp(_conv3x3_moments_fwd, _conv3x3_moments_bwd)
