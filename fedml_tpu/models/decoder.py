"""A decoder-only language model built from a configuration.

Where ``models/transformer.py`` is one fixed block (LayerNorm, learned
positions, GELU MLP, tied head), this one reads what a published
``config.json`` says: pre-norm RMSNorm blocks and an untied head, and a layer
at a time a sequence mixer and an MLP of the kinds the configuration names.
It shares ``MultiHeadAttention`` (and so the attention policy and the flash
kernels) with the transformer, and trains through ``make_local_update`` like
any ``ModelBundle``.

Mixer kinds (``DecoderConfig.layer_types``, one a layer):

- ``sliding_attention`` / ``full_attention``: softmax attention with q heads
  that share k/v heads and a head size of its own, a window or none; rotary
  positions (plain or YaRN, by the layer's type) where the configuration has
  ``rope_parameters``, no positional encoding where it has none.
- ``latent_attention``: softmax attention whose keys and values are expanded
  from a normed low-rank latent, plus a key part that all heads share; q and k
  heads of ``qk_nope_head_dim + qk_rope_head_dim``, v heads of ``v_head_dim``
  (``LatentQKV``, handed to ``MultiHeadAttention`` as its projection).
- ``sparse_attention`` (a configuration with ``sa_config``): softmax attention
  over the keys a learned indexer picks for each query, the ``topk`` highest of
  ``sum_j w_j relu(qI_j . kI)`` over the causal positions (``Indexer``,
  ``ops/sparse_select.py``, ``chosen_keys``); one set a token for every head.
  The choice is discrete: the next-token loss gives the indexer's leaves a
  gradient of exactly zero, and q, k, v that of a softmax over the set held
  fixed.
- ``linear_attention``: the gated delta rule with a per-channel decay
  (``ops/linear_attention.py``), behind short causal depthwise convolutions,
  with low-rank decay and output gates and a gated RMSNorm a head
  (``LinearAttention``).  Position comes from the recurrence.
- ``mamba``: the Mamba-2 mixer (``Mamba2Mixer``), the SSD recurrence with a
  scalar decay a head (``ops/ssd.py``) behind a biased causal depthwise
  convolution, with a skip and an RMSNorm by groups gated by ``silu(z)``.
- ``none``: no mixer; the layer is its MLP alone.  A layer of
  ``hybrid_override_pattern`` holds a mixer alone or an MLP alone (MLP kind
  ``none``): one pre-norm, one sublayer, one residual.

Any softmax kind RMS-norms its q and k heads before the rotation where the
configuration says ``qk_norm``.  Rotary positions come from ``rope_parameters``
or from a top-level ``rope_theta`` (with ``rope_scaling``: an ``mrope_section``
block is plain RoPE on text, whose three position streams coincide); a
top-level ``rope_theta`` rotates every softmax kind unless ``rope_layer_types``
names the kinds it rotates, and a kind left out has no positional encoding
(``DecoderConfig.rope`` has no entry for it).

What a block may have besides, each a field of ``DecoderConfig`` that is off
unless the configuration's keys state it (``from_dict`` says which), and with
all of them off the parameter tree and the program are what they were:
``attn_gate``, a sigmoid gate of the layer's normed input on the attention
output before the output projection (``MultiHeadAttention``'s ``gate``);
``post_norm``, an RMSNorm **after** the mixer and after the MLP, inside the
residual branch (``x + norm(mixer(norm(x)))``: four norms a layer, the two new
ones named ``post_attn_norm`` and ``post_mlp_norm``, their weights starting at
``POST_NORM_INIT``);
``embed_scale``, a factor on the embedding's output (the function's own: the
table's gradient carries it, and the table's initial std is its inverse, so
that the scaled output has unit variance); ``selection_bias``, a leaf
``[routed]`` of the expert layer that shifts which experts a token chooses and
not how it weighs them, outside the gradient.

MLP kinds (``mlp_types``): ``sparse``, the expert layer below, with a shared
expert (a ``GatedMLP`` every token passes) added outside the routed sum where
the configuration has one; ``dense``, one ``GatedMLP``.  Where
``mlp_act`` is ``relu2`` both are ``Relu2MLP`` and the experts too are a
squared ReLU without a gate; where ``moe_latent_size`` is set the routed
experts work in that width, between a down-projection of the normed input
and an up-projection of their weighted sum.  Router kinds
(``router_activation``): ``softmax`` over all routed experts, or ``sigmoid``
scores; either renormalises the chosen ``top_k`` where ``norm_topk_prob``
and scales them by ``routed_scaling_factor``.  With a ``selection_bias``
the ``top_k`` are the largest of ``scores + bias`` and the weights come from
the scores without it.

The expert layer (``ExpertLayer``) is told which experts it holds.  The
router keeps its full width and picks ``top_k`` of all experts; this
layer computes what its own experts add for the tokens routed to them and
leaves out the rest, which is what expert parallelism asks of one chip's
layer (without its exchange: nothing here stands in for the other chips).
Holding every expert, it is the whole layer.  No token is dropped: the row
buffer follows the rows routed.  A share of the experts sorts its rows into
a buffer of twice what a level router sends it, each expert's group from a
row tile of the grouped products, when the count of a call allows, and runs
every held expert over every token, with no buffer, when it does not; where
that buffer would be no shorter than the worst case,
``tokens x min(top_k, experts held)``, the layer is one path through the
worst case (``buffer_capacities``).  Rows move between tokens and buffer
through ``ops/expert_rows.py``'s pair, which moves the rows held and builds
no array of ``tokens x top_k`` rows where its kernel runs.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.base import COUNTERS, ModelBundle
from fedml_tpu.models.transformer import (
    AttnFn, MultiHeadAttention, RMSNorm, _default_attn, scaled_init, scoped,
)
from fedml_tpu.obs import scopes
from fedml_tpu.ops.expert_rows import (
    from_buffer, sort_assignments, sorted_route, to_buffer,
)
from fedml_tpu.ops.linear_attention import gated_delta_rule, short_causal_conv
from fedml_tpu.ops.sparse_select import select_topk, tiles_scored
from fedml_tpu.ops.ssd import ssd_chunked

SLIDING, FULL = "sliding_attention", "full_attention"
LINEAR, LATENT = "linear_attention", "latent_attention"
SELECTED = "sparse_attention"  # keys chosen by an indexer
SSM = "mamba"  # a Mamba-2 state-space mixer
SPARSE, DENSE = "sparse", "dense"
NONE = "none"  # a layer without a mixer, or without an MLP
# a layer kind a character of ``hybrid_override_pattern`` names: (mixer, MLP)
PATTERN = {"M": (SSM, NONE), "*": (FULL, NONE), "E": (NONE, SPARSE),
           "-": (NONE, DENSE)}
# the activation of an MLP or an expert: a SiLU gate, or a squared ReLU
SILU_GATED, RELU2 = "silu_gated", "relu2"
# the scalar counters ``DecoderLM`` sows into ``COUNTERS`` in train mode
ASSIGNMENTS_HELD = "moe_assignments_held"  # token-expert pairs on held experts
EXPERT_TOKENS_MAX = "moe_expert_tokens_max"  # the fullest held expert's rows
ROWS_BUFFERED = "moe_rows_buffered"  # rows of the buffer the layer took
ROWS_COMPUTED = "moe_rows_computed"  # rows the grouped products computed
# a linear-attention layer's mean log decay over tokens, heads and channels
KDA_LOG_DECAY_MEAN = "kda_log_decay_mean"
# an SSM layer's mean of ``dt * A`` (the log decay) over tokens and heads
SSM_LOG_DECAY_MEAN = "ssm_log_decay_mean"
# a SELECTED layer's count of 512 x 512 causal tiles that hold a chosen pair
ATTN_TILES_LIVE = "attn_tiles_live"
# a SELECTED layer's count of tiles whose index scores were computed: the
# causal ones where the choice ran in its kernel, every tile as lax ops
SELECT_TILES_SCORED = "select_tiles_scored"
# an expert layer with a selection bias: tokens whose chosen set is not the
# ``top_k`` largest of the scores alone
TOKENS_BIAS_MOVED = "moe_tokens_bias_moved"
# what the weights of the norms after the sublayers start from, not 1: a
# fresh attention's output is close to one mean of ``v`` for every token, and
# a norm whose weight is 1 makes that a common component of unit size in
# every token's stream, under which a fresh router sends some experts
# several times their share (measured: PERF.md §6, PR 41); a tenth is about
# what a fresh sublayer without such a norm adds to the stream
POST_NORM_INIT = 0.1


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # one a layer: SLIDING, FULL, LINEAR, LATENT, SELECTED, SSM or NONE
    layer_types: Tuple[str, ...]
    sliding_window: int
    rope: Tuple[Tuple[str, Tuple[Tuple[str, object], ...]], ...]  # by layer type
    rms_norm_eps: float
    moe_intermediate_size: int
    num_experts_routed: int  # the router's width
    experts_held: Tuple[int, ...]  # ids of the experts this layer computes
    top_k: int
    norm_topk_prob: bool
    max_len: int
    remat: bool = False
    # one a layer: SPARSE, DENSE or NONE; () all SPARSE
    mlp_types: Tuple[str, ...] = ()
    intermediate_size: int = 0  # the dense MLP's width
    shared_expert_size: int = 0  # the shared expert's width; 0: none
    router_activation: str = "softmax"  # or "sigmoid"
    routed_scaling_factor: float = 1.0
    # LINEAR layers: heads, head size; LINEAR and SSM layers: convolution
    # taps, the scan's chunk
    linear_heads: int = 0
    linear_head_dim: int = 0
    conv_kernel: int = 4
    chunk: int = 64
    # LATENT layers: the latent's rank and the three head sizes
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    qk_norm: bool = False  # RMSNorm on q and k heads (softmax kinds)
    # SELECTED layers: index heads, their size, keys a query keeps
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    index_topk: int = 0
    attn_gate: bool = False  # a sigmoid gate on the attention output
    post_norm: bool = False  # an RMSNorm after the mixer and after the MLP
    # a factor on the embedding's output; the table's std is its inverse
    embed_scale: float = 1.0
    # the router's selection bias: None, no leaf; else the std of its initial
    # values (a leaf [routed], added to the scores for the choice alone)
    selection_bias: Optional[float] = None
    # SSM layers: heads, head size, groups of B and C, state size, a bias on
    # the convolution
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv_bias: bool = False
    mlp_act: str = SILU_GATED  # of the dense MLP and every expert
    moe_latent_size: int = 0  # routed experts in this width; 0: hidden size
    # on the initial weights of every residual branch's last projection
    residual_scale: float = 1.0

    @classmethod
    def from_dict(cls, c: dict) -> "DecoderConfig":
        """From a ``config.json``'s keys.  Depth is ``n_layer`` or
        ``num_hidden_layers``.  A layer's mixer comes from
        ``linear_attn_config`` (``kda_layers`` and ``full_attn_layers``,
        numbered from 1; a full layer is LATENT where ``kv_lora_rank`` is
        set), from ``layer_types``, cycled to the depth, or, where the
        configuration carries ``sa_config`` and no ``layer_types``, is SELECTED
        throughout; its MLP from
        ``mlp_layer_types`` or from ``first_k_dense_replace`` (or
        ``num_dense_layers``) leading dense layers.  ``num_experts_routed``
        defaults to ``num_experts``, ``experts_held`` to the first
        ``num_experts`` ids, and ``linear_attn_heads`` to
        ``linear_attn_config.num_heads``.

        The router reads either dialect of its keys:
        ``moe_router_activation_func`` or ``score_func``; ``norm_topk_prob``,
        ``moe_renormalize`` or ``route_norm``; ``routed_scaling_factor`` or
        ``route_scale``; experts in groups (``n_group`` / ``topk_group``
        other than 1) are refused.
        ``selection_bias_init_std`` states a selection bias and the std its
        leaf starts from.

        A ``hybrid_override_pattern`` gives every layer, a character each
        (``PATTERN``): ``M`` a Mamba-2 mixer alone (``mamba_num_heads`` of
        ``mamba_head_dim``, ``n_groups`` of B and C, ``ssm_state_size``,
        ``conv_kernel``, ``chunk_size``, ``use_conv_bias``), ``*``
        full attention alone, ``E`` the expert layer alone, ``-`` the dense
        MLP alone.  ``mlp_hidden_act`` ``relu2`` makes every MLP and expert
        a squared ReLU without a gate; ``moe_latent_size`` puts the routed
        experts in that width between a down- and an up-projection;
        ``moe_shared_expert_intermediate_size`` is the shared expert's width
        where given; ``n_routed_experts`` the router's width and
        ``layer_norm_epsilon`` every norm's eps.  ``rescale_prenorm_residual``
        starts the last projection of every residual branch (the Mamba and
        attention output projections, an MLP's down-projection, the routed
        sum's up-projection) at its lecun-normal init over
        ``sqrt(num_hidden_layers)``: GPT-2's rule, which the key names
        (a linear-attention layer's output projection is not scaled).

        ``mup_enabled`` scales the embedding's output by
        ``sqrt(hidden_size)``.  ``attention_gate`` switches the gate on
        the attention output, ``post_norm`` the norms after the sublayers;
        ``rope_layer_types`` names the layer kinds a top-level ``rope_theta``
        rotates (default: every softmax kind)."""
        depth = c.get("n_layer", c.get("num_hidden_layers"))
        linear = c.get("linear_attn_config") or {}
        pattern = c.get("hybrid_override_pattern")
        if pattern:
            if not set(pattern) <= set(PATTERN):
                raise ValueError(f"hybrid_override_pattern {pattern!r}: only "
                                 f"the layers {sorted(PATTERN)} are built")
            kinds, mlp = zip(*(PATTERN[ch] for ch in pattern))
        elif linear:
            kinds = [LINEAR if i + 1 in linear["kda_layers"]
                     else LATENT if c.get("kv_lora_rank") else FULL
                     for i in range(depth)]
        else:
            kinds = c.get("layer_types") or [
                SELECTED if c.get("sa_config") else FULL]
        leading = c.get("first_k_dense_replace", c.get("num_dense_layers", 0))
        if not pattern:
            mlp = c.get("mlp_layer_types") or [
                DENSE if i < leading else SPARSE for i in range(depth)]
            if not set(mlp) <= {SPARSE, DENSE}:
                raise ValueError(f"MLP kinds {sorted(set(mlp))}: only "
                                 f"{SPARSE!r} and {DENSE!r} are built")
        act = {"silu": SILU_GATED, "relu2": RELU2}.get(
            c.get("mlp_hidden_act", "silu"))
        if act is None or c.get("mamba_hidden_act", "silu") != "silu" \
                or c.get("mamba_proj_bias"):
            raise ValueError(
                f"mlp_hidden_act {c.get('mlp_hidden_act')!r} / "
                f"mamba_hidden_act {c.get('mamba_hidden_act')!r} / "
                f"mamba_proj_bias {c.get('mamba_proj_bias')!r}: only a SiLU "
                "gate or relu2 MLP and a SiLU Mamba mixer without "
                "projection biases are built")
        if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
            raise ValueError(
                f"n_group {c.get('n_group')} / topk_group "
                f"{c.get('topk_group')}: a choice among groups of experts is "
                "not built, only a plain top-k (both 1)")
        routed = c.get("num_experts_routed",
                       c.get("n_routed_experts", c["num_experts"]))
        held = tuple(c.get("experts_held", range(c["num_experts"])))
        if len(held) != c["num_experts"] or not all(
                0 <= e < routed for e in held):
            raise ValueError(f"experts_held {held} against num_experts "
                             f"{c['num_experts']} of {routed} routed")
        rope = c.get("rope_parameters") or {}
        if not rope and "rope_theta" in c and not c.get("mla_use_nope"):
            # the older top-level keys.  ``rope_scaling`` may carry an
            # ``mrope_section``: three position streams that coincide on text
            scaling = c.get("rope_scaling") or {}
            kind = scaling.get("rope_type", scaling.get("type", "default"))
            if kind != "default":
                raise ValueError(f"rope_scaling of rope_type {kind!r}: only "
                                 "'default' is read from the top-level keys")
            rope = {"rope_type": "default", "rope_theta": c["rope_theta"]}
        # none: no positional encoding
        if "rope_type" in rope:  # one block for every layer type it rotates
            rope = {kind: rope for kind in c.get(
                "rope_layer_types", (SLIDING, FULL, SELECTED))}
        sparse = c.get("sa_config") or {}
        return cls(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            num_layers=depth, num_heads=c["num_attention_heads"],
            num_kv_heads=c.get("num_key_value_heads",
                               c["num_attention_heads"]),
            head_dim=c.get("head_dim",
                           c["hidden_size"] // c["num_attention_heads"]),
            layer_types=tuple(kinds[i % len(kinds)] for i in range(depth)),
            sliding_window=c.get("sliding_window"),
            rope=tuple(sorted((k, tuple(sorted(v.items())))
                              for k, v in rope.items())),
            rms_norm_eps=c.get("rms_norm_eps",
                               c.get("layer_norm_epsilon", 1e-6)),
            moe_intermediate_size=c["moe_intermediate_size"],
            num_experts_routed=routed, experts_held=held,
            top_k=c.get("num_experts_per_tok", c.get("num_experts_per_token")),
            norm_topk_prob=c.get("norm_topk_prob", c.get(
                "moe_renormalize", c.get("route_norm", True))),
            max_len=c.get("n_positions", c.get("max_position_embeddings")),
            remat=c.get("remat", False),
            mlp_types=tuple(mlp[i % len(mlp)] for i in range(depth)),
            intermediate_size=c.get("intermediate_size", 0),
            shared_expert_size=c.get(
                "moe_shared_expert_intermediate_size",
                c.get("num_shared_experts", 0) * c["moe_intermediate_size"]),
            router_activation=c.get("moe_router_activation_func",
                                    c.get("score_func", "softmax")),
            routed_scaling_factor=c.get("routed_scaling_factor",
                                        c.get("route_scale", 1.0)),
            linear_heads=c.get("linear_attn_heads", linear.get("num_heads", 0)),
            linear_head_dim=linear.get("head_dim", 0),
            conv_kernel=linear.get("short_conv_kernel_size",
                                   c.get("conv_kernel", 4)),
            chunk=c.get("chunk", c.get("chunk_size", 64)),
            kv_lora_rank=c.get("kv_lora_rank") or 0,
            qk_nope_head_dim=c.get("qk_nope_head_dim", 0),
            qk_rope_head_dim=c.get("qk_rope_head_dim", 0),
            v_head_dim=c.get("v_head_dim", 0),
            qk_norm=c.get("qk_norm", False),
            indexer_heads=sparse.get("indexer_num_heads", 0),
            indexer_head_dim=sparse.get("indexer_head_dim", 0),
            index_topk=sparse.get("topk", 0),
            attn_gate=c.get("attention_gate", False),
            post_norm=c.get("post_norm", False),
            embed_scale=(math.sqrt(c["hidden_size"])
                         if c.get("mup_enabled") else 1.0),
            selection_bias=c.get("selection_bias_init_std"),
            ssm_heads=c.get("mamba_num_heads", 0),
            ssm_head_dim=c.get("mamba_head_dim", 0),
            ssm_groups=c.get("n_groups", 1),
            ssm_state=c.get("ssm_state_size", 0),
            ssm_conv_bias=c.get("use_conv_bias", False),
            mlp_act=act,
            moe_latent_size=c.get("moe_latent_size") or 0,
            residual_scale=(1 / math.sqrt(c["num_hidden_layers"])
                            if c.get("rescale_prenorm_residual") else 1.0),
        )


# -- rotary positions ---------------------------------------------------------

def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """``theta ** (-2i / head_dim)``, i = 0 .. head_dim / 2 - 1."""
    return theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)


def yarn_correction_range(head_dim, theta, original_max, beta_fast,
                          beta_slow) -> Tuple[int, int]:
    """(low, high): the pair indices between which YaRN ramps from the
    published frequencies to the interpolated ones."""
    def dim_of(rotations):
        return head_dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), head_dim - 1))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32,
                  beta_slow: float = 1) -> np.ndarray:
    """YaRN's frequencies: pairs that turn fast keep theirs, pairs that
    turn slowly over the original context are divided by ``factor``, with
    a linear ramp between.  Static: the same at every length."""
    low, high = yarn_correction_range(head_dim, theta, original_max,
                                      beta_fast, beta_slow)
    keep = 1 - np.clip((np.arange(head_dim // 2) - low)
                       / max(high - low, 1e-3), 0, 1)
    base = rope_inv_freq(head_dim, theta)
    return base * keep + base / factor * (1 - keep)


def make_rope_fn(params: dict, head_dim: int) -> Callable:
    """x [B, L, H, D] -> x rotated by its position, in the rotate-half form
    (pairs ``(i, i + D/2)``), from a ``rope_parameters`` block."""
    theta = params["rope_theta"]
    if params.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(
            head_dim, theta, params["factor"],
            params["original_max_position_embeddings"],
            params.get("beta_fast", 32), params.get("beta_slow", 1))
        scale = params.get("attention_factor",
                           0.1 * math.log(params["factor"]) + 1)
    elif params.get("rope_type", "default") == "default":
        inv_freq, scale = rope_inv_freq(head_dim, theta), 1.0
    else:
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    inv_freq = jnp.asarray(inv_freq, jnp.float32)

    def rope(x):
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
        cos = (jnp.cos(angle) * scale)[None, :, None, :]
        sin = (jnp.sin(angle) * scale)[None, :, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)

    return rope


# -- layers -------------------------------------------------------------------

# A group on row tiles wastes half a tile on average, where groups one after
# another compute a shared tile once for each: on row tiles a 256-row tile
# runs the nine products of a layer-step 1-9 % faster than a 512-row one in
# every decoder cell (256-1024 rows a group), though a tile runs ~10 % slower
# a row (PERF.md §6, ``benchmark/tools/expert_tiles_bench.py``)
ALIGNED_ROW_TILE = 256


def gmm_tiling(m: int, k: int, n: int, aligned: bool = False):
    """(rows, contraction, columns) tile of the grouped-product kernel for
    ``[m, k] @ [groups, k, n]``, or None where it does not take the shape:
    the largest row tile of 512, 256, 128 that divides ``m``, and no longer
    than ``ALIGNED_ROW_TILE`` where every group starts on a row tile
    (``aligned``), and, along ``k`` and ``n``, the widest multiple of 128 up
    to 1152 that divides the dim.  At the published expert shapes (65536 x
    2304 x 896) a (512, 1152, 896) tile ran forward + backward 2.2x faster
    than (128, 128, 128) tiles times nine (PERF.md §6)."""
    def tile(dim):
        return next((t for t in range(1152, 0, -128) if dim % t == 0), 0)

    largest = ALIGNED_ROW_TILE if aligned else 512
    tiling = (next((t for t in (512, 256, 128)
                    if t <= largest and m % t == 0), 0), tile(k), tile(n))
    return tiling if all(tiling) else None



def rows_computed(group_sizes, tile: int):
    """Rows the grouped products compute for groups of ``group_sizes`` rows
    laid one after another from row 0: the row tiles of ``tile`` rows their
    grid visits, times ``tile``.  By ``megablox``'s rule
    (``make_group_metadata``) a group covers its first row rounded down to a tile through its last
    rounded up, so a tile that two groups share is computed once for each,
    and an empty group covers nothing.  Where every group starts on a tile
    and takes whole tiles it is their sum."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, -(-ends // tile) - starts // tile, 0)
    return tiles.sum() * tile


def _grouped_dot(rows, weights, group_sizes, aligned):
    """``rows[r] @ weights[e]`` for the rows of group ``e``: groups lie one
    after another from row 0 (each on a row tile where ``aligned``); rows
    past their total cost no matmul and hold nothing a caller may read.

    On a TPU this is the Pallas grouped matmul (``megablox.gmm``), whose
    grid holds only the row tiles the groups cover (``rows_computed``),
    forward and in both gradients.  ``lax.ragged_dot`` compiles there to a
    product of every row with every group under a mask when the sizes are
    not constants (38 ms a product at the published shapes against 0.5,
    PERF.md §6); it is what other backends and shapes the kernel does not
    tile run."""
    tiling = gmm_tiling(rows.shape[0], *weights.shape[1:], aligned)
    if jax.default_backend() == "tpu" and tiling:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, weights, group_sizes, rows.dtype, tiling)
    return jax.lax.ragged_dot(rows, weights, group_sizes)


# The short row buffer over the rows a level router sends to the experts
# held.  PR 32 measured the held experts at 0.95-1.11 of their share under a
# level router and at 0.3-1.6 under a collapsed one: twice the share holds
# both, and a call that routes more runs without a buffer.
BUFFER_HEADROOM = 2
ROW_TILE = 512  # the grouped product's largest row tile (``gmm_tiling``)


def buffer_capacities(tokens: int, top_k: int, held: int,
                      routed: int) -> Tuple[int, int]:
    """(short, worst case) rows of the expert layer's buffer, from the shapes
    alone.  Worst case: every token's whole top-k on held experts.  Short:
    ``BUFFER_HEADROOM`` x the ``tokens x top_k x held / routed`` rows of a
    level router, rounded up to whole row tiles.  Where short >= worst case
    the layer is one path through the worst case, else it takes the short
    buffer when a call's count fits it and ``_every_expert`` when not."""
    share = -(-tokens * top_k * held // routed)
    short = -(-BUFFER_HEADROOM * share // ROW_TILE) * ROW_TILE
    return short, tokens * min(top_k, held)


@jax.custom_vjp
def _kept_for(computed, kept):
    """``kept``, a value of ``computed`` from an earlier pass, with
    ``computed``'s gradient: what computed it runs no second time (nothing
    reads it), its backward does."""
    return kept


_kept_for.defvjp(lambda computed, kept: (kept, None),
                 lambda _, g: (g, None))


def _activated(product, gated: bool):
    """An expert's activation in float32 of its input products, ``product(i)``
    the i-th: gated, ``silu(gate) * up`` (products 0 and 1), else
    ``relu(up) ** 2`` (product 0).  A product is asked for where it is used,
    so ``_every_expert`` computes the gate's before the up product's, as the
    accepted cells' programs (and their compile cache's keys) have it."""
    if gated:
        return (jax.nn.silu(product(0).astype(jnp.float32))
                * product(1).astype(jnp.float32))
    return jnp.square(jax.nn.relu(product(0).astype(jnp.float32)))


def _expert_rows(capacity, aligned, kept, x, top_p, weights, local, order,
                 rank, group_sizes, group_rows):
    """(the held experts' part of the layer's output [T, h] in the experts'
    dtype, the buffers a backward reads again: the rows, their weights, their
    input products and the activated product) through a row buffer of
    ``capacity`` rows (static; at least ``group_rows.sum()``), each group on
    a row tile of the grouped products where ``aligned`` (static).

    ``x`` [T, h], ``top_p`` [T, k]; ``weights`` the stacked expert matrices:
    the input products' (gate and up, or up alone: ``_activated``), then the
    output product's; ``local`` [T, k] is the local index of
    each assignment's expert (the count of experts held: not here), ``order``
    and ``rank`` lay the assignments out in the buffer (``sort_assignments``),
    ``group_sizes`` [E] counts each held expert's assignments and
    ``group_rows`` [E] the rows its group takes: the size the grouped products
    are handed, whose pad rows hold zeros in every buffer and add exact zeros
    to every gradient.  ``kept``: those buffers from an earlier pass, to be
    read and not computed again, or None.

    Rows cross between tokens and buffer four times, and each crossing moves
    the rows held and nothing else (``ops/expert_rows.py``): ``to_buffer``
    for the experts' input and, as ``from_buffer``'s backward, for the
    output's cotangent; ``from_buffer`` for the experts' output and, as
    ``to_buffer``'s backward, for the input's cotangent.  None passes through
    an array of ``T x k`` rows where the kernel runs.  The routing weights
    cross as ``C`` scalars out of ``T x k``, and come back as a scatter of
    ``C`` into ``T x k``."""
    keep = iter(kept or ())
    *w_in, w_down = weights

    def buffer(computed):
        return _kept_for(computed, next(keep)) if kept else computed

    with jax.named_scope(scopes.MOE_DISPATCH):
        route = sorted_route(local, order, rank, group_sizes, capacity,
                             group_rows)
        rows = buffer(to_buffer(x.astype(w_down.dtype), route))
        # a pad's entry of ``order`` is past the assignments: it reads 0
        row_w = buffer(jnp.where(
            route.row_live, top_p.reshape(-1).at[order[:capacity]].get(
                unique_indices=True, mode="fill", fill_value=0), 0))

    with jax.named_scope(scopes.MOE_EXPERTS):
        products = [buffer(_grouped_dot(rows, w, group_rows, aligned))
                    for w in w_in]
        act = buffer((_activated(products.__getitem__, len(w_in) == 2)
                      * row_w[:, None]).astype(w_down.dtype))
        out = _grouped_dot(act, w_down, group_rows, aligned)

    with jax.named_scope(scopes.MOE_COMBINE):
        y = from_buffer(out, route)
    return y, (rows, row_w, *products, act)


def _every_expert(x, top_p, weights, local):
    """The same output with no row buffer: every held expert over every token,
    weighted by the token's routing weight for it or 0 (``local`` [T, k]: the
    local index of each assignment's expert).  The products of the worst case
    (every token's whole top-k here) whatever was routed, as plain matmuls
    an expert at a time: no sort, no gather, no kernel of its own to compile,
    and each expert computes its forward again in the backward.  (As three
    matmuls batched over the experts it held 0.22 GiB more and, never run,
    cost the cell 3 % of its tokens/s: PERF.md §6, PR 33.)"""
    rows = x.astype(weights[-1].dtype)

    def one(y, expert):
        e, *w_in, down_w = expert
        weight = jnp.where(local == e, top_p, 0).sum(axis=-1, keepdims=True)
        act = _activated(lambda i: rows @ w_in[i], len(w_in) == 2) * weight
        return y + (act.astype(down_w.dtype) @ down_w).astype(jnp.float32), None

    with jax.named_scope(scopes.MOE_EXPERTS):
        y, _ = jax.lax.scan(
            jax.checkpoint(one), jnp.zeros(x.shape, jnp.float32),
            (jnp.arange(weights[0].shape[0]), *weights))
    return y.astype(weights[-1].dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _expert_rows_fitted(capacity, aligned, fits, *operands):
    """``_expert_rows`` through ``capacity`` rows where ``fits`` (a traced
    bool), else ``_every_expert``.

    Differentiated by hand, a ``cond`` forward and a ``cond`` backward.  JAX's
    own rule splits each branch into a part that makes residuals and a part
    that reads them, hands the second ``cond`` the union of both branches'
    residuals, and has each branch zero-fill the other's and copy the
    operands it reads again.  Here the buffered branch keeps its own
    buffers, the other keeps nothing, and neither copies an operand."""
    return _fitted_fwd(capacity, aligned, fits, *operands)[0]


def _fitted_fwd(capacity, aligned, fits, *operands):
    buffered = functools.partial(_expert_rows, capacity, aligned, None)
    _, kept = jax.eval_shape(buffered, *operands)

    def unbuffered(x, top_p, weights, local, *_):
        return (_every_expert(x, top_p, weights, local),
                tuple(jnp.zeros(b.shape, b.dtype) for b in kept))

    y, kept = jax.lax.cond(fits, buffered, unbuffered, *operands)
    return y, (fits, operands, kept)


def _fitted_bwd(capacity, aligned, res, g):
    fits, (*floats, local, order, rank, group_sizes, group_rows), kept = res

    def grads(path):
        return jax.vjp(path, *floats)[1](g)

    d_floats = jax.lax.cond(
        fits,
        lambda: grads(lambda *floats: _expert_rows(
            capacity, aligned, kept, *floats, local, order, rank,
            group_sizes, group_rows)[0]),
        lambda: grads(lambda *floats: _every_expert(*floats, local)))
    return (None, *d_floats, None, None, None, None, None)


_expert_rows_fitted.defvjp(_fitted_fwd, _fitted_bwd)


class ExpertLayer(nn.Module):
    """Dropless top-k mixture of gated SiLU experts over the experts held
    (``expert_act`` ``RELU2``: squared-ReLU experts ``relu(x U)^2 D``, no
    ``gate`` leaf).  With a ``latent_size`` the experts take and give rows of
    that width: the normed input goes down to it (``latent_down``) before the
    rows go to the buffer, and the weighted sum comes back up
    (``latent_up``) once, under ``model.moe_latent``; the router reads the
    input at full width.

    ``x`` [B, L, h] float32 (the normed input).  Router and selection run in
    float32; the expert products run in the dtype of the expert weights.
    Returns (``sum over e in top-k and held of w_e f_e(x)`` in the experts'
    dtype, the four counters as float32 scalars).  The scores are a softmax
    over all routed experts or a sigmoid an expert (``router_activation``);
    the ``top_k`` largest are chosen, renormalised to sum to 1 where
    ``norm_topk_prob`` and multiplied by ``routed_scaling_factor``.

    ``selection_bias`` (None: none, and no leaf) makes the choice the
    ``top_k`` largest of ``scores + bias``, ``bias`` a leaf [routed] whose
    initial values are normal with that std, while the weights stay the
    chosen experts' scores **without** it.  The bias is outside the gradient:
    it enters under ``stop_gradient`` and only through the discrete choice,
    so the loss gives its leaf exactly zero (a rule of the model's own would
    move it between steps; none is built).  A fifth counter then counts the
    tokens whose chosen set is not the ``top_k`` largest of the scores alone
    (one whose least chosen score is under its greatest unchosen one).

    The row buffer is as long as ``buffer_capacities`` says.  Where the short
    one is shorter than the worst case, every held expert's group in it
    starts on a row tile of the grouped products (``gmm_tiling``'s at the
    buffer's length, at most ``ALIGNED_ROW_TILE``), and they are handed each group's size rounded up to
    that tile: a tile that two groups would share is computed once, not once
    for each.  The pad rows are zeros, add zeros to every gradient and reach
    no token.  A ``lax.cond`` on this call's rows with their pads
    (``_expert_rows_fitted``) takes the short buffer when they fit, and else
    runs every held expert over every token (``_every_expert``: the worst
    case's products, no buffer, nothing kept for the backward, which
    computes the forward again).  Both paths compute the same sums, and no
    token is dropped on either; ``moe_rows_buffered`` says which ran: the
    short buffer's rows, or ``tokens x experts held``, and
    ``moe_rows_computed`` how many rows the products computed
    (``rows_computed``, or ``tokens x experts held``).  Where the short
    buffer is no shorter (every expert held) there is no branch and the
    buffer is the worst case, its groups one after another from row 0.

    Two transfers carry rows between the tokens [T, h] and the buffer [C, h],
    each the other's backward (``ops/expert_rows.py``): ``to_buffer``, a
    gather of C rows out of T, and ``from_buffer``, a token's held rows added
    in float32: on a TPU one kernel pass over token tiles that copies the
    held rows' windows and nothing else, elsewhere a gather over the slots.
    Forward and backward use each twice, and none passes through ``T x k``
    rows where the kernel runs.

    Not under a ``vmap`` over clients (``client_axis_impl="vmap"``):
    ``lax.ragged_dot`` refuses stacked expert weights ("ragged_dot vmap ...
    NYI"), and a batched ``cond`` is a select that runs both paths; the
    default client loop is a scan."""

    num_experts_routed: int
    experts_held: Tuple[int, ...]
    top_k: int
    intermediate: int
    norm_topk_prob: bool = True
    router_activation: str = "softmax"  # or "sigmoid": a score an expert
    routed_scaling_factor: float = 1.0  # on the chosen experts' weights
    selection_bias: Optional[float] = None  # the std of the bias leaf's init
    expert_act: str = SILU_GATED  # or RELU2: no gate leaf
    latent_size: int = 0  # experts in this width between two projections
    out_scale: float = 1.0  # on the initial weights of the last projection

    @nn.compact
    def __call__(self, x):
        B, L, h = x.shape
        T, k, held = B * L, self.top_k, len(self.experts_held)
        A = T * k  # assignments
        width = self.latent_size or h  # the experts' input and output
        short, worst = buffer_capacities(T, k, held, self.num_experts_routed)
        init = nn.initializers.lecun_normal()
        stacked = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                               batch_axis=0)
        router = self.param("router", init, (h, self.num_experts_routed))
        if self.expert_act not in (SILU_GATED, RELU2):
            raise ValueError(f"unknown expert activation {self.expert_act!r}")
        last = 1.0 if self.latent_size else self.out_scale
        weights = tuple(
            self.param(name, stacked, (held, width, self.intermediate))
            for name in (("gate", "up") if self.expert_act == SILU_GATED
                         else ("up",))
        ) + (self.param("down", scaled_init(last, stacked),
                        (held, self.intermediate, width)),)
        x = x.reshape(T, h)

        with jax.named_scope(scopes.MOE_ROUTER):
            logits = jnp.dot(x, router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if self.router_activation == "softmax":
                scores = jax.nn.softmax(logits, axis=-1)
            elif self.router_activation == "sigmoid":
                scores = jax.nn.sigmoid(logits)
            else:
                raise ValueError(
                    f"unknown router activation {self.router_activation!r}")
            moved = None
            if self.selection_bias is None:
                top_p, top_e = jax.lax.top_k(scores, k)
            else:
                bias = self.param(
                    "selection_bias",
                    nn.initializers.normal(self.selection_bias),
                    (self.num_experts_routed,))
                shifted = scores + jax.lax.stop_gradient(
                    bias.astype(jnp.float32))
                kth, top_e = jax.lax.top_k(shifted, k)
                top_p = jnp.take_along_axis(scores, top_e, axis=-1)
                # moved: the least score chosen is under the greatest not
                chosen = shifted >= kth[:, -1:]
                moved = (jnp.where(chosen, scores, jnp.inf).min(axis=-1)
                         < jnp.where(chosen, -jnp.inf, scores).max(axis=-1)
                         ).sum().astype(jnp.float32)
            if self.norm_topk_prob:
                top_p = top_p / top_p.sum(axis=-1, keepdims=True)
            if self.routed_scaling_factor != 1.0:
                top_p = top_p * self.routed_scaling_factor
            # for a caller that asks for "intermediates": the selection
            self.sow("intermediates", "top_e", top_e)

        # the grouped products' row tile at the buffer's length: the short
        # buffer starts every group on one, the worst case has no room for pads
        aligned = short < worst
        tiling = gmm_tiling(min(short, worst), width, self.intermediate,
                            aligned)
        tile = tiling[0] if tiling else 1
        align = tile if aligned else 1
        with jax.named_scope(scopes.MOE_DISPATCH):
            # local index of each assignment's expert; ``held`` = not here
            local_of = np.full((self.num_experts_routed,), held, np.int32)
            local_of[list(self.experts_held)] = np.arange(held)
            local = jnp.asarray(local_of)[top_e]  # [T, k]
            group_sizes = (local.reshape(A, 1) == jnp.arange(held)).sum(
                axis=0, dtype=jnp.int32)
            routed = group_sizes.sum()
            group_rows = (group_sizes if align == 1
                          else -(-group_sizes // align) * align)
            # a counting sort by expert: held assignments first, by expert,
            # each group followed by its pads
            order, rank = sort_assignments(local, group_rows - group_sizes,
                                           align - 1)
            computed = rows_computed(group_rows, tile)

        rows = x
        if self.latent_size:
            with jax.named_scope(scopes.MOE_LATENT):
                w_in = self.param("latent_down", init, (h, width))
                rows = x.astype(w_in.dtype) @ w_in
        operands = (rows, top_p, weights, local, order, rank, group_sizes,
                    group_rows)
        if not aligned:
            y, _ = _expert_rows(worst, False, None, *operands)
            buffered = float(worst)
        else:
            fits = group_rows.sum() <= short
            y = _expert_rows_fitted(short, True, fits, *operands)
            buffered = jnp.where(fits, float(short), float(T * held))
            computed = jnp.where(fits, computed, T * held)

        counters = {
            ASSIGNMENTS_HELD: routed.astype(jnp.float32),
            EXPERT_TOKENS_MAX: group_sizes.max().astype(jnp.float32),
            ROWS_BUFFERED: jnp.asarray(buffered, jnp.float32),
            ROWS_COMPUTED: computed.astype(jnp.float32),
        }
        if moved is not None:
            counters[TOKENS_BIAS_MOVED] = moved
        y = y.astype(weights[-1].dtype)
        if self.latent_size:
            with jax.named_scope(scopes.MOE_LATENT):
                y = y @ self.param("latent_up", scaled_init(self.out_scale),
                                   (width, h))
        return y.reshape(B, L, h), counters


class GatedMLP(nn.Module):
    """``(silu(x Wg) * (x Wu)) Wd`` with no bias: the dense MLP of a layer,
    and the shared expert beside the routed ones."""

    width: int
    out_scale: float = 1.0  # on the down-projection's initial weights

    @nn.compact
    def __call__(self, x):
        gate = nn.Dense(self.width, use_bias=False, name="gate")(x)
        up = nn.Dense(self.width, use_bias=False, name="up")(x)
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        return nn.Dense(x.shape[-1], use_bias=False, name="down",
                        kernel_init=scaled_init(self.out_scale))(
            act.astype(x.dtype))


class Relu2MLP(nn.Module):
    """``relu(x Wu)^2 Wd`` with no bias and no gate: the dense MLP and the
    shared expert where the configuration's activation is ``relu2``."""

    width: int
    out_scale: float = 1.0  # on the down-projection's initial weights

    @nn.compact
    def __call__(self, x):
        up = nn.Dense(self.width, use_bias=False, name="up")(x)
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
        return nn.Dense(x.shape[-1], use_bias=False, name="down",
                        kernel_init=scaled_init(self.out_scale))(
            act.astype(x.dtype))


def _mlp(c: DecoderConfig, width: int, name: str) -> nn.Module:
    return (GatedMLP if c.mlp_act == SILU_GATED else Relu2MLP)(
        width, c.residual_scale, name=name)



class LatentQKV(nn.Module):
    """The projections of a latent-attention layer, for ``MultiHeadAttention``
    to call in place of its fused one: ``q`` a head of ``nope + rope`` from
    ``x``; a ``rank + rope`` wide down-projection whose first ``rank``
    columns are normed and expanded to every head's ``nope`` key part and
    its value, and whose last ``rope`` columns are a key part all heads
    share.  Nothing is rotated: the layer has no positional encoding."""

    num_heads: int
    rank: int
    nope: int
    rope: int
    v_head_dim: int
    eps: float

    @nn.compact
    def __call__(self, x):
        (B, L, _), H = x.shape, self.num_heads
        with jax.named_scope(scopes.MLA_PROJ):
            q = nn.Dense(H * (self.nope + self.rope), use_bias=False,
                         name="q")(x).reshape(B, L, H, self.nope + self.rope)
            c = nn.Dense(self.rank + self.rope, use_bias=False, name="kv_a")(x)
            latent = RMSNorm(self.eps, name="kv_norm")(
                c[..., :self.rank]).astype(x.dtype)
            kv = nn.Dense(H * (self.nope + self.v_head_dim), use_bias=False,
                          name="kv_b")(latent).reshape(
                B, L, H, self.nope + self.v_head_dim)
            shared = jnp.broadcast_to(c[:, :, None, self.rank:],
                                      (B, L, H, self.rope))
            k = jnp.concatenate([kv[..., :self.nope], shared], axis=-1)
        return q, k, kv[..., self.nope:]


class Indexer(nn.Module):
    """The index projections of a SELECTED layer, for ``MultiHeadAttention``
    to hand its attention function: ``x`` [B, L, h] -> (``qI`` [B, L, Hi, di],
    ``kI`` [B, L, di], ``w`` [B, L, Hi]).  ``qI = x WqI`` a head,
    ``kI = LayerNorm(x WkI)``, one index key a token for all index heads,
    ``w = x Ww``; ``qI`` and ``kI`` rotated over all their channels.  The
    projections, the norm and the rotation are float32 at full precision
    whatever ``x``'s dtype (the choice they feed is a comparison of close
    numbers); ``qI`` and ``kI`` are then rounded to ``x``'s dtype, the
    operands of the index scores' products (``ops/sparse_select.py``: on the
    chip in bf16 the 16-head product is 4/5 of the choice's time at float32
    precision, and rounding its operands flips 0.02 % more of the chosen pairs
    against the reference than the residual stream's own rounding does, 0.35-
    1.0 %: PERF.md §6, PR 39); ``w`` stays float32."""

    num_heads: int
    head_dim: int
    eps: float
    rope_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        (B, L, _), H, d = x.shape, self.num_heads, self.head_dim
        operand = x.dtype
        x = x.astype(jnp.float32)

        def dense(width, name):
            return nn.Dense(width, use_bias=False, name=name,
                            precision=jax.lax.Precision.HIGHEST)(x)

        with jax.named_scope(scopes.ATTN_INDEXER):
            q = dense(H * d, "q").reshape(B, L, H, d)
            k = nn.LayerNorm(epsilon=self.eps, name="k_norm")(dense(d, "k"))
            w = dense(H, "w")
            if self.rope_fn is not None:
                q = self.rope_fn(q)
                k = self.rope_fn(k[:, :, None, :])[:, :, 0]
        return q.astype(operand), k.astype(operand), w


def chosen_keys(attn: AttnFn, topk: int) -> AttnFn:
    """``attn`` over the ``topk`` keys an indexer picks a query, as
    ``MultiHeadAttention`` calls the attention function of a layer with an
    ``indexer``: ``index`` = one example's (qI, kI, w).  The choice
    (``select_topk``) goes to ``attn`` as ``keep=`` and ``tiles=``; beside
    the output come the count of tiles that hold a chosen pair and the count
    of tiles the choice scored."""
    def fn(q, k, v, causal, index):
        with jax.named_scope(scopes.ATTN_SELECT):
            keep, tiles = select_topk(*index, topk)
            live = tiles.sum().astype(jnp.float32)
            scored = jnp.float32(tiles_scored(*index[:2]))
        with jax.named_scope(scopes.ATTN_SPARSE):
            out = attn(q, k, v, causal, keep=keep, tiles=tiles)
        return out, {ATTN_TILES_LIVE: live, SELECT_TILES_SCORED: scored}

    return fn


def _log_uniform(low: float, high: float, transform: Callable) -> Callable:
    """An initializer: ``transform`` of ``exp(U[log low, log high])``."""
    def init(key, shape, dtype=jnp.float32):
        return transform(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(low), math.log(high)))
        ).astype(dtype)

    return init


def _conv_taps(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


class LinearAttention(nn.Module):
    """The gated delta rule's layer.  ``x`` [B, L, h], the normed input.

    ``q``, ``k``, ``v`` = SiLU of a short causal depthwise convolution of
    their projections, ``q`` and ``k`` L2-normed a head (``q`` also divided
    by ``sqrt(head_dim)``); a log decay a channel ``g = -exp(A_log) *
    softplus(Wfb (Wfa x) + dt_bias)`` and a ``beta = sigmoid(x Wbeta)`` a
    head; ``ops.linear_attention.gated_delta_rule``; the output RMS-normed a
    head, gated by ``sigmoid(Wgb (Wga x))`` and projected back.  Convolution,
    norms, decay and gates are float32; the scan's state products take ``x``'s
    dtype.  Returns (the output [B, L, h], the mean of ``g`` as a float32
    scalar)."""

    num_heads: int
    head_dim: int
    conv_kernel: int
    chunk: int
    eps: float

    @nn.compact
    def __call__(self, x):
        (B, L, h), H, d = x.shape, self.num_heads, self.head_dim
        f32 = jnp.float32

        def dense(width, name, t=x):
            return nn.Dense(width, use_bias=False, name=name)(t)

        def mixed(name):
            taps = self.param(f"{name}_conv", _conv_taps,
                              (self.conv_kernel, H * d))
            u = short_causal_conv(dense(H * d, f"{name}_proj").astype(f32),
                                  taps.astype(f32))
            return jax.nn.silu(u).reshape(B, L, H, d)

        def l2_normed(t):
            return t * jax.lax.rsqrt((t * t).sum(axis=-1, keepdims=True)
                                     + 1e-6)

        with jax.named_scope(scopes.KDA_PROJ):
            q = l2_normed(mixed("q")) / math.sqrt(d)
            k = l2_normed(mixed("k"))
            v = mixed("v").astype(x.dtype)
        with jax.named_scope(scopes.KDA_GATES):
            a_log = self.param("A_log", _log_uniform(1, 16, jnp.log), (H,))
            dt_bias = self.param(
                "dt_bias", _log_uniform(
                    1e-3, 1e-1, lambda dt: dt + jnp.log(-jnp.expm1(-dt))),
                (H * d,))
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                dense(H * d, "f_b", dense(d, "f_a")).astype(f32)
                + dt_bias.astype(f32)).reshape(B, L, H, d)
            beta = jax.nn.sigmoid(dense(H, "b_proj").astype(f32))
            gate = jax.nn.sigmoid(
                dense(H * d, "g_b", dense(d, "g_a")).astype(f32))
        with jax.named_scope(scopes.KDA_SCAN):
            o = jax.vmap(functools.partial(gated_delta_rule, chunk=self.chunk)
                         )(q, k, v, g, beta)
        with jax.named_scope(scopes.KDA_OUT):
            o = RMSNorm(self.eps, name="o_norm")(o).reshape(B, L, H * d) * gate
            return dense(h, "o_proj", o.astype(x.dtype)), g.mean()


# a fresh Mamba-2 mixer's ``dt``: log-uniform in [1e-3, 1e-1], floored at
# 1e-4 (Mamba-2's ``dt_min``, ``dt_max``, ``dt_init_floor``)
DT_INIT = (1e-3, 1e-1, 1e-4)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer.  ``x`` [B, L, h], the normed input; with ``d = H
    P``:

    ``[z | xBC | dt] = x W_in`` (widths d, d + 2 G N, H; no bias);
    ``xBC = silu(conv(xBC) + b)``, a causal depthwise convolution of
    ``conv_kernel`` taps (``short_causal_conv``) and its bias where
    ``conv_bias``; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``
    and ``A = -exp(A_log)`` a head; ``y = ssd(x, dt, A, B, C) + D x``
    (``ops/ssd.py``, chunks of ``chunk``); ``y`` gated by ``silu(z)`` and
    RMS-normed in ``groups`` groups of ``d / G`` channels, times a weight
    [d]; projected back.  Convolution, ``dt``, the decay, the skip and the
    gated norm are float32; the scan's products take ``x``'s dtype.
    Initialised as Mamba-2 is: ``A_log = log U[1, 16]``, ``dt_bias`` the
    inverse softplus of a ``dt`` log-uniform in ``DT_INIT``'s range and
    floored at its floor, ``D = 1``.  Returns (the output [B, L, h], the
    mean of ``dt A`` as a float32 scalar)."""

    num_heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    conv_bias: bool
    eps: float
    out_scale: float = 1.0  # on out_proj's initial weights

    @nn.compact
    def __call__(self, x):
        (B, L, h), H, P = x.shape, self.num_heads, self.head_dim
        G, N, d = self.groups, self.state, self.num_heads * self.head_dim
        f32 = jnp.float32
        low, high, floor = DT_INIT

        def inverse_softplus(dt):
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))

        with jax.named_scope(scopes.SSM_PROJ):
            z, xbc, dt = jnp.split(
                nn.Dense(2 * d + 2 * G * N + H, use_bias=False,
                         name="in_proj")(x), [d, 2 * d + 2 * G * N], axis=-1)
            xbc = short_causal_conv(xbc.astype(f32), self.param(
                "conv", _conv_taps, (self.conv_kernel, d + 2 * G * N)
            ).astype(f32))
            if self.conv_bias:
                xbc = xbc + self.param("conv_bias", _conv_taps,
                                       (d + 2 * G * N,)).astype(f32)
            u, b, c = jnp.split(jax.nn.silu(xbc), [d, d + G * N], axis=-1)
            u = u.reshape(B, L, H, P)
            dt = jax.nn.softplus(dt.astype(f32) + self.param(
                "dt_bias", _log_uniform(low, high, inverse_softplus), (H,)
            ).astype(f32))
            A = -jnp.exp(self.param("A_log", _log_uniform(1, 16, jnp.log),
                                    (H,)).astype(f32))
        with jax.named_scope(scopes.SSM_SCAN):
            y = jax.vmap(functools.partial(ssd_chunked, chunk=self.chunk),
                         in_axes=(0, 0, None, 0, 0))(
                u.astype(x.dtype), dt, A,
                b.reshape(B, L, G, N).astype(x.dtype),
                c.reshape(B, L, G, N).astype(x.dtype))
        with jax.named_scope(scopes.SSM_OUT):
            skip = self.param("D", nn.initializers.ones, (H,)).astype(f32)
            y = ((y.astype(f32) + skip[:, None] * u).reshape(B, L, d)
                 * jax.nn.silu(z.astype(f32))).reshape(B, L, G, d // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + self.eps)
            y = y.reshape(B, L, d) * self.param(
                "norm", nn.initializers.ones, (d,)).astype(f32)
            out = nn.Dense(h, use_bias=False, name="out_proj",
                           kernel_init=scaled_init(self.out_scale))(
                y.astype(x.dtype))
        return out, (dt * A).mean()


class DecoderBlock(nn.Module):
    cfg: DecoderConfig
    kind: str
    attn_fn: Optional[AttnFn] = None
    mlp: str = SPARSE

    @nn.nowrap
    def mixer(self, a):
        """The layer's sequence mixer over the normed input: (its output, its
        counters)."""
        c = self.cfg
        if self.kind == SSM:
            y, log_decay = Mamba2Mixer(
                c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state,
                c.conv_kernel, c.chunk, c.ssm_conv_bias, c.rms_norm_eps,
                c.residual_scale)(a)
            return y, {SSM_LOG_DECAY_MEAN: log_decay}
        if self.kind == LINEAR:
            y, log_decay = LinearAttention(
                c.linear_heads, c.linear_head_dim, c.conv_kernel, c.chunk,
                c.rms_norm_eps)(a)
            return y, {KDA_LOG_DECAY_MEAN: log_decay}
        attn_fn = self.attn_fn or _default_attn
        if self.kind == LATENT:
            return MultiHeadAttention(
                c.num_heads, attn_fn=scoped(attn_fn, scopes.ATTN_LATENT),
                qkv=LatentQKV(c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                              c.qk_rope_head_dim, c.v_head_dim,
                              c.rms_norm_eps, parent=None),
                out_scale=c.residual_scale)(a), {}
        sliding = self.kind == SLIDING
        rope = dict(dict(c.rope).get(self.kind) or {})

        def rope_fn(head_dim):
            return make_rope_fn(rope, head_dim) if rope else None

        shared = dict(
            num_kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            rope_fn=rope_fn(c.head_dim),
            qk_norm=c.rms_norm_eps if c.qk_norm else None, gate=c.attn_gate,
            out_scale=c.residual_scale)
        if self.kind == SELECTED:
            return MultiHeadAttention(
                c.num_heads, attn_fn=chosen_keys(attn_fn, c.index_topk),
                indexer=Indexer(c.indexer_heads, c.indexer_head_dim,
                                c.rms_norm_eps, rope_fn(c.indexer_head_dim),
                                parent=None), **shared)(a)
        return MultiHeadAttention(
            c.num_heads,
            attn_fn=scoped(attn_fn, scopes.ATTN_SLIDING if sliding
                            else scopes.ATTN_FULL),
            window=c.sliding_window if sliding else None, **shared)(a), {}

    @nn.compact
    def __call__(self, x):
        """A pre-norm residual sublayer for the mixer and one for the MLP;
        a layer whose mixer or MLP is ``NONE`` has the other alone."""
        c = self.cfg

        def after(y, name):
            """The sublayer's output through its own norm, where a block has
            one, before the residual add."""
            if not c.post_norm:
                return y
            with jax.named_scope(scopes.NORM):
                return RMSNorm(c.rms_norm_eps, POST_NORM_INIT, name=name)(
                    y).astype(x.dtype)

        counters = {}
        if self.kind != NONE:
            with jax.named_scope(scopes.NORM):
                a = RMSNorm(c.rms_norm_eps)(x).astype(x.dtype)
            y, counters = self.mixer(a)
            x = x + after(y, "post_attn_norm")
        if self.mlp == NONE:
            return x, counters
        with jax.named_scope(scopes.NORM):
            b = RMSNorm(c.rms_norm_eps)(x)
        if self.mlp == DENSE:
            with jax.named_scope(scopes.MLP_DENSE):
                y = _mlp(c, c.intermediate_size, "mlp")(b.astype(x.dtype))
            return x + after(y, "post_mlp_norm"), counters
        y, routed = ExpertLayer(
            c.num_experts_routed, c.experts_held, c.top_k,
            c.moe_intermediate_size, c.norm_topk_prob, c.router_activation,
            c.routed_scaling_factor, c.selection_bias, c.mlp_act,
            c.moe_latent_size, c.residual_scale,
        )(b)
        y = y.astype(x.dtype)
        if c.shared_expert_size:
            with jax.named_scope(scopes.MOE_SHARED):
                y = y + _mlp(c, c.shared_expert_size, "shared_expert")(
                    b.astype(x.dtype))
        return x + after(y, "post_mlp_norm"), {**counters, **routed}


class DecoderLM(nn.Module):
    cfg: DecoderConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.cfg
        if x.shape[1] > c.max_len:
            raise ValueError(
                f"sequence length {x.shape[1]} exceeds max_len {c.max_len}")
        # unit-variance embeddings (torch's default).  Under the 1/sqrt(h) of
        # flax's, a fresh model's attention output, a running mean of v that
        # neighbouring tokens share, outweighs the token's own embedding;
        # every router then sees one common input and a few experts take
        # nearly every token (measured: PERF.md §6, PR 32).  Where the
        # configuration scales the embedding's output, the table starts at
        # the inverse of the factor, and the scaled output has unit variance
        with jax.named_scope(scopes.EMBED):
            h = nn.Embed(c.vocab_size, c.hidden_size, name="wte",
                         embedding_init=nn.initializers.normal(
                             1.0 / c.embed_scale))(x.astype(jnp.int32))
            if c.embed_scale != 1.0:
                h = (h.astype(jnp.float32) * c.embed_scale).astype(h.dtype)
        block_cls = nn.remat(DecoderBlock) if c.remat else DecoderBlock
        totals = {}
        for i, kind in enumerate(c.layer_types):
            mlp = c.mlp_types[i] if c.mlp_types else SPARSE
            h, counters = block_cls(c, kind, self.attn_fn, mlp,
                                    name=f"Block_{i}")(h)
            summed = {n: totals.get(n, 0.0) + v for n, v in counters.items()}
            # a layer of one sublayer passes the other kind's sums on; a
            # layer of two keeps only the names it counts (PERF.md §7)
            totals = {**totals, **summed} if NONE in (kind, mlp) else summed
        if train and not self.is_initializing():
            for n, v in totals.items():
                self.sow(COUNTERS, n, v, reduce_fn=lambda _, new: new,
                         init_fn=lambda: jnp.zeros((), jnp.float32))
        with jax.named_scope(scopes.NORM):
            h = RMSNorm(c.rms_norm_eps, name="norm_f")(h).astype(h.dtype)
        with jax.named_scope(scopes.HEAD):
            return nn.Dense(c.vocab_size, use_bias=False, name="lm_head")(h)


def decoder_lm(config, attn_fn: Optional[AttnFn] = None) -> ModelBundle:
    """``config``: a ``DecoderConfig`` or a ``config.json``-style dict."""
    cfg = config if isinstance(config, DecoderConfig) \
        else DecoderConfig.from_dict(config)
    return ModelBundle(
        module=DecoderLM(cfg, attn_fn), input_shape=(cfg.max_len,),
        input_dtype=jnp.int32, has_counters=True,
    )
