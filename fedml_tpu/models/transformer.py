"""Transformer LM with pluggable attention — the long-context model
family.

The reference's NLP ceiling is a 2-layer LSTM over 80-char windows
(``model/nlp/rnn.py``, SURVEY.md §5.7).  This decoder-only transformer
is the rebuild's long-context extension: its attention is an injected
function, so the SAME module runs

- single-device exact blockwise attention (O(L) memory), or
- ring attention over a sequence-sharded mesh axis
  (``parallel.ring_attention.ring_attention`` under ``shard_map``),

with no model code changes.  Pre-LN blocks, learned positional
embeddings, weight-tied output head.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models.base import ModelBundle
from fedml_tpu.obs import scopes
from fedml_tpu.parallel.ring_attention import blockwise_attention

# (q, k, v, causal) over [L, H, D] per example; a layer with a window also
# passes ``window=``, k, v may hold fewer heads than q, and v's head size
# may differ from q's and k's.  A layer whose keys are chosen from the data
# passes ``keep=`` ([L, L] int8: 1 where the query's row sees the key's
# column, causal) and ``tiles=`` (the table of 512-tiles that hold a kept
# pair): ``ops/sparse_select.py``.  ``MultiHeadAttention`` itself hands an
# ``indexer``'s per-example output on as ``index=`` and takes (output, a dict
# of per-example scalars) back: the function it is given then makes the
# choice (``models/decoder.py`` ``chosen_keys``).
AttnFn = Callable


def lax_attention(q, k, v, causal, window=None, keep=None, tiles=None):
    """The lax blockwise scan under the ``AttnFn`` signature: the policy's
    fallback, and what a caller whose heads are sharded by GSPMD passes
    as ``attn_fn`` (a ``pallas_call`` has no partitioning rule, so XLA
    would gather q, k, v and run every head on every chip).

    With a choice of keys (``keep``; the scan reads no tile table) the call
    runs under ``jax.checkpoint``, as the scan then runs each kv block: a
    query sees keys all along its row, and neither the carries a block nor
    the probability blocks a plain scan saves for its backward (all of
    [H, L, L]: 4.3 GB a layer at 32 heads of 8192 in bf16) outlive the
    layer's own backward."""
    if keep is None:
        return blockwise_attention(q, k, v, causal=causal, block_size=512,
                                   window=window)
    del tiles
    return jax.checkpoint(functools.partial(
        blockwise_attention, causal=causal, block_size=512, window=window,
    ))(q, k, v, keep=keep)


def _default_attn(q, k, v, causal, window=None, keep=None, tiles=None):
    """Single-device attention policy, from what the call can see:

    - on a TPU, for a shape the fused kernels take (``pick_block`` finds
      a block that divides L, ``head_group`` a lane layout for the head
      size, or for q/k's and v's where they differ) and inputs in a
      16-bit compute dtype: the Pallas flash
      kernels (``ops/flash_attention.py``), forward and backward, which
      keep scores and probabilities in VMEM and save only ``o`` and
      ``lse``.  At the benchmark cells' shape (bf16 [8, 1024, 20, 64])
      they run forward + backward 3.1x faster than the lax scan below
      (PERF.md §6: 8.01 ms a layer, PR 26, against 2.55 for one
      gradient call, PR 30);
    - float32 inputs take the kernels from L = 2048 on, in 512-blocks,
      where the lax path's saved probability blocks no longer fit (17.6
      GB observed for a 4 x 8192 batch); below that they keep the lax
      path, which is also the benchmark reference's attention;
    - other backends (the kernels are Mosaic/TPU; the CPU runs them only
      in interpret mode) and ragged lengths: the lax blockwise scan.

    The model cannot see a sharding: a caller whose heads GSPMD shards
    passes ``lax_attention`` as ``attn_fn`` (``experiments/run.py
    --mesh``).
    """
    from fedml_tpu.ops.flash_attention import (
        flash_attention, head_group, pick_block,
    )

    L, H, D = q.shape
    Dv = v.shape[-1]
    block = pick_block(L, D)
    if q.dtype.itemsize == 2:
        fits = block > 0
    else:
        fits = block == 512 and L >= 2048
    # k/v heads shared among q heads: one head a column block
    groups = head_group(H, D, Dv) if k.shape[1] == H else (
        D % 128 == 0 and Dv % 128 == 0)
    if fits and groups and jax.default_backend() == "tpu":
        return flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block,
            window=window, keep=keep, tiles=tiles,
        )
    return lax_attention(q, k, v, causal, window, keep, tiles)


def scoped(attn: AttnFn, name: str) -> AttnFn:
    """``attn`` with its ops under the scope ``name``."""
    def fn(*args, **kwargs):
        with jax.named_scope(name):
            return attn(*args, **kwargs)

    return fn


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * g``, computed and returned in float32
    (the caller rounds it where it wants to); ``g`` starts at ``init``."""

    eps: float = 1e-6
    init: float = 1.0

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.constant(self.init),
                       (x.shape[-1],))
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * g.astype(jnp.float32)


def scaled_init(scale: float = 1.0, init=None):
    """``init`` (lecun-normal, ``nn.Dense``'s own, by default) times ``scale``:
    the init of a residual branch's last projection where a configuration
    scales it."""
    init = init or nn.initializers.lecun_normal()
    if scale == 1.0:
        return init
    return lambda key, shape, dtype=jnp.float32: init(key, shape, dtype) * scale


class MultiHeadAttention(nn.Module):
    """One fused q/k/v projection, the attention function under ``vmap``
    over the batch, the output projection.  By default every q head has its
    own k/v head of size ``E // num_heads``; ``num_kv_heads`` shares each
    k/v head among ``num_heads // num_kv_heads`` q heads, ``head_dim`` sets
    a head size that is not ``E // num_heads``, ``rope_fn`` rotates q and k
    ([B, L, H, D] -> the same) and ``window`` is handed to ``attn_fn``.
    ``qkv``, a module ``x -> (q, k, v)`` with parameters of its own, takes
    the fused projection's place: its v heads may be of another size than its
    q and k heads, and the output projection reads what comes back.

    ``qk_norm`` (an ``eps``; off by default) RMS-norms every q head and every
    k head over its channels before the rotation, one weight [D] for q and
    one for k.  ``indexer``, a module ``x -> (qI, kI, w)`` with parameters of
    its own, makes the layer one whose keys are chosen from the data: its
    per-example output reaches ``attn_fn`` inside the ``vmap`` as ``index=``,
    ``attn_fn`` returns (output, {name: scalar}) and so does this module, the
    scalars summed over the batch.

    ``gate`` (off by default) makes the attention gated: a fourth projection
    of the layer's input, ``z = x Wz`` as [B, L, H, Dv] (a ``Dense`` named
    ``gate``, no bias, of its own: not part of the fused q/k/v), whose sigmoid
    multiplies the attention function's output element by element, a head and
    channel at a time, before the output projection:
    ``y = (concat_i(o_i) * sigmoid(z)) Wo``.  The sigmoid and the multiply
    run in float32 outside the ``vmap``, under ``model.attn_gate`` with the
    projection; with the gate off the module is what it was, op for op."""

    num_heads: int
    attn_fn: Optional[AttnFn] = None
    causal: bool = True
    num_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rope_fn: Optional[Callable] = None
    window: Optional[int] = None
    qkv: Optional[nn.Module] = None
    qk_norm: Optional[float] = None
    indexer: Optional[nn.Module] = None
    gate: bool = False
    out_scale: float = 1.0  # on the output projection's initial weights

    @nn.compact
    def __call__(self, x):
        B, L, E = x.shape
        H = self.num_heads
        G = self.num_kv_heads or H
        D = self.head_dim or E // H
        if self.qkv is not None:
            q, k, v = self.qkv(x)
        else:
            with jax.named_scope(scopes.ATTN_PROJ):
                qkv = nn.Dense((H + 2 * G) * D, use_bias=False)(x)
                q, k, v = jnp.split(qkv.reshape(B, L, H + 2 * G, D),
                                    [H, H + G], axis=2)
        if self.qk_norm is not None:
            with jax.named_scope(scopes.NORM):
                q = RMSNorm(self.qk_norm, name="q_norm")(q).astype(x.dtype)
                k = RMSNorm(self.qk_norm, name="k_norm")(k).astype(x.dtype)
        if self.rope_fn is not None:
            with jax.named_scope(scopes.ROPE):
                q, k = self.rope_fn(q), self.rope_fn(k)
        attn = self.attn_fn or _default_attn
        if self.window is not None:
            attn = functools.partial(attn, window=self.window)
        sums = None
        if self.indexer is not None:
            out, scalars = jax.vmap(
                lambda a, b, c, i: attn(a, b, c, self.causal, index=i)
            )(q, k, v, self.indexer(x))
            sums = {n: s.sum() for n, s in scalars.items()}
        else:
            out = jax.vmap(lambda a, b, c: attn(a, b, c, self.causal))(q, k, v)
        if self.gate:
            with jax.named_scope(scopes.ATTN_GATE):
                z = nn.Dense(H * out.shape[-1], use_bias=False, name="gate")(x)
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    z.astype(jnp.float32).reshape(out.shape))).astype(x.dtype)
        with jax.named_scope(scopes.ATTN_PROJ):
            y = nn.Dense(E, use_bias=False,
                         kernel_init=scaled_init(self.out_scale))(
                out.reshape(B, L, H * out.shape[-1]))
        return y if sums is None else (y, sums)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        E = x.shape[-1]
        with jax.named_scope(scopes.NORM):
            h = nn.LayerNorm()(x)
        x = x + MultiHeadAttention(self.num_heads, scoped(
            self.attn_fn or _default_attn, scopes.ATTN_FULL))(h)
        with jax.named_scope(scopes.NORM):
            h = nn.LayerNorm()(x)
        with jax.named_scope(scopes.MLP_DENSE):
            h = nn.Dense(self.mlp_ratio * E)(h)
            h = nn.Dense(E)(nn.gelu(h))
        return x + h


class TransformerLM(nn.Module):
    vocab_size: int = 256
    embed_dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 2048
    attn_fn: Optional[AttnFn] = None
    pos_offset_fn: Optional[Callable] = None  # (local_len) -> global offset
    # gradient rematerialization: checkpoint each Block's activations
    # and recompute them in the backward pass — trades ~1/3 more FLOPs
    # for O(layers) less live-activation HBM, the standard lever that
    # lets one v5e chip train widths past GPT-2-Large (width 1536 OOMs
    # at batch 8x1024 without it — PROFILE.md).  Parameter tree is
    # unchanged (nn.remat is a lifted transform), pinned by
    # tests/test_models.py::test_transformer_remat_same_function
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, L = x.shape
        tok = nn.Embed(self.vocab_size, self.embed_dim, name="wte")
        pos0 = self.pos_offset_fn(L) if self.pos_offset_fn else 0
        if isinstance(pos0, int) and pos0 + L > self.max_len:
            raise ValueError(
                f"sequence length {L} exceeds max_len {self.max_len}"
            )
        wpe = nn.Embed(self.max_len, self.embed_dim, name="wpe")
        with jax.named_scope(scopes.EMBED):
            h = tok(x.astype(jnp.int32))
            h = h + wpe(pos0 + jnp.arange(L))[None]
        # explicit names keep the parameter tree identical with and
        # without remat (nn.remat would auto-name "CheckpointBlock_i")
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.num_layers):
            h = block_cls(self.num_heads, attn_fn=self.attn_fn,
                          name=f"Block_{i}")(h)
        # named so partition-rule tables (parallel/partition.py) can
        # address the final norm distinctly from the blocks' auto-named
        # LayerNorm_{0,1} — the GPT convention
        with jax.named_scope(scopes.NORM):
            h = nn.LayerNorm(name="ln_f")(h)
        # weight-tied head
        with jax.named_scope(scopes.HEAD):
            return tok.attend(h)


def transformer_lm(
    vocab_size=256, embed_dim=128, num_heads=4, num_layers=2, seq_len=256,
    attn_fn: Optional[AttnFn] = None, max_len: Optional[int] = None,
    remat: bool = False,
) -> ModelBundle:
    return ModelBundle(
        module=TransformerLM(
            vocab_size=vocab_size, embed_dim=embed_dim, num_heads=num_heads,
            num_layers=num_layers, max_len=max_len or seq_len,
            attn_fn=attn_fn, remat=remat,
        ),
        input_shape=(seq_len,),
        input_dtype=jnp.int32,
    )
