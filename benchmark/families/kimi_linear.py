"""Model family ``kimi_linear``: the configuration-driven decoder of
``fedml_tpu/models/decoder.py`` with gated delta-rule linear-attention (KDA)
layers and NoPE latent-attention (MLA) layers mixed by ``linear_attn_config``,
a leading dense gated MLP, and a sigmoid-routed dropless top-k expert layer
beside a shared expert that holds a share of the experts; at the sizes of a
configuration file in the published ``config.json``'s key names.  Depth is
``n_layer``; the KDA head count of this chip's share is ``linear_attn_heads``
(the published one lives inside ``linear_attn_config`` and is kept there).

Beside what every family exposes (``families/transformer_lm.py`` lists it):

  plain_bundle(config)      the plain reference's forward pass as a bundle
  layer_counts(config)      {"kda", "mla", "dense", "sparse"}: layers of a kind
  linear_attn_flops_per_token(config)   the recurrence's own forward FLOPs of
                            one token through one KDA layer, every head
  linear_attn_bytes_per_token(config)   its least HBM bytes a pass
  latent_pair_flops(config) (forward, backward) FLOPs of a causal (query, key)
                            pair of one MLA q head
  attention_pairs_per_sample(config), attention_heads(config), held_share(config),
  expert_flops_per_assignment(config), expert_train_bytes(config, ...)
                            what the accepted ``flash_roofline`` and
                            ``expert_*`` readers call, for the PR that appends
                            this family's cell to their lists
"""

from __future__ import annotations

from benchmark.families import kimi_linear_plain
# an expert is the same gated MLP in both decoder families, in the same keys
from benchmark.families.mellum_moe import (  # noqa: F401
    expert_flops_per_assignment, expert_train_bytes,
)
# token ids from ``vocab_size`` (here the vocabulary's slice) with next-token
# targets, and a sample's tokens as its units: as for any language model
from benchmark.families.transformer_lm import (  # noqa: F401
    make_samples, units_per_sample,
)


def build_bundle(config: dict):
    from fedml_tpu.models.decoder import decoder_lm

    return decoder_lm(config)


def plain_bundle(config: dict):
    return kimi_linear_plain.PlainBundle(config)


def layer_counts(config: dict) -> dict:
    kinds = kimi_linear_plain.layer_kinds(config)
    return {name: sum(name in kind for kind in kinds)
            for name in ("kda", "mla", "dense", "sparse")}


def _kda_shape(config: dict) -> tuple:
    """(heads here, head size)."""
    return (kimi_linear_plain.linear_heads(config),
            config["linear_attn_config"]["head_dim"])


def linear_attn_flops_per_token(config: dict) -> int:
    """Forward FLOPs of the recurrence itself for one token through one KDA
    layer: a head's three matrix-vector products with its [d, d] state
    (``S~^T k``, the rank-one update, ``S^T q``), 2 a multiply-add.  The
    decay's multiply and whatever a chunked form computes besides earn
    nothing."""
    heads, d = _kda_shape(config)
    return heads * 3 * 2 * d * d


def linear_attn_bytes_per_token(config: dict) -> int:
    """Least HBM bytes of one pass over one token of one KDA layer: q, k, v
    and g in and o out once, 2 bytes each."""
    heads, d = _kda_shape(config)
    return 2 * 5 * heads * d


def latent_pair_flops(config: dict) -> tuple:
    """(forward, backward) FLOPs a causal pair costs one MLA q head: scores
    over the q/k head size and values over the v head size forward; backward
    three products over the first (scores again, dq, dk) and two over the
    second (dp, dv)."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    return 2 * (qk + v), 2 * (3 * qk + 2 * v)


def attention_pairs_per_sample(config: dict) -> int:
    """(query, key) pairs of one sequence that the causal mask lets through,
    over every MLA layer: a query at i sees i + 1 keys."""
    L = config["n_positions"]
    return layer_counts(config)["mla"] * (L * (L + 1) // 2)


def attention_heads(config: dict) -> tuple:
    """(q heads, head size) as ``flash_roofline`` takes them: it charges a
    pair 4 x and 10 x one head size, so the size is the mean of the q/k and
    the v head sizes (exact forward, 4 % under ``latent_pair_flops``'s
    backward: the share reads low)."""
    fwd, _ = latent_pair_flops(config)
    return config["num_attention_heads"], fwd // 4


def held_share(config: dict) -> float:
    """Held token-expert assignments a token an expert layer, in expectation
    under a uniform router: the published top-k times the share of experts
    here."""
    return (config["num_experts_per_token"] * config["num_experts"]
            / config["num_experts_routed"])


def _dense_products(config: dict) -> list:
    """(in, out) of every dense matmul a token passes, through every layer
    and the head: mixer projections, router, shared expert, dense MLP."""
    h = config["hidden_size"]
    heads, d = _kda_shape(config)
    H = config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v = config["qk_nope_head_dim"], config["v_head_dim"]
    f = config["moe_intermediate_size"] * config["num_shared_experts"]
    kda = 3 * [(h, heads * d)] + 2 * [(h, d), (d, heads * d)] + [
        (h, heads), (heads * d, h)]
    mla = [(h, H * (nope + rope)), (h, rank + rope), (rank, H * (nope + v)),
           (H * v, h)]
    sparse = [(h, config["num_experts_routed"])] + 2 * [(h, f)] + [(f, h)]
    dense = 2 * [(h, config["intermediate_size"])] + [
        (config["intermediate_size"], h)]
    n = layer_counts(config)
    return (n["kda"] * kda + n["mla"] * mla + n["sparse"] * sparse
            + n["dense"] * dense + [(h, config["vocab_size"])])


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward FLOPs of one token by op class (2 a multiply-add).  ``matmul``
    is exactly the dense products; the routed experts' grouped products, the
    MLA scores (the causal pairs only) and the recurrence (its own count, not
    the chunked form's) have keys of their own: the scan's inner products are
    XLA dots whose seconds land in the trace's ``matmul`` class while their
    FLOPs do not, so ``matmul_roofline`` reads low here."""
    n = layer_counts(config)
    attention = (attention_pairs_per_sample(config) * latent_pair_flops(
        config)[0] * config["num_attention_heads"] / config["n_positions"])
    return {"matmul": 2 * sum(i * o for i, o in _dense_products(config)),
            "expert": (n["sparse"] * held_share(config)
                       * expert_flops_per_assignment(config)),
            "attention": attention,
            "linear_attention": n["kda"] * linear_attn_flops_per_token(config)}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """Least HBM bytes of the dense matmuls of one training step per token:
    every weight read forward and backward and its gradient written (2
    bytes), over the step's ``batch_units``; activations in and out of each
    matmul once a pass."""
    products = _dense_products(config)
    weights = sum(i * o for i, o in products)
    acts = sum(i + o for i, o in products)
    return {"matmul": 2 * (3 * weights / batch_units + 3 * acts)}
