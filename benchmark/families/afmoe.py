"""Model family ``afmoe``: the configuration-driven decoder of
``fedml_tpu/models/decoder.py`` with gated softmax attention (a sigmoid gate of
the layer's input on the attention output), windowed layers with rotary
positions mixed with full layers that have no positional encoding, q and k
heads normed, a norm after each sublayer as well as before, embeddings scaled
by ``sqrt(hidden_size)``, leading dense gated MLPs, and a sigmoid-routed
dropless top-k expert layer beside a shared expert whose choice a selection
bias shifts, holding a share of the experts; at the sizes of a configuration
file in the published ``config.json``'s key names.  Depth is ``n_layer``.

Beside what every family exposes (``families/transformer_lm.py`` lists it):

  plain_bundle(config)      the plain reference's forward pass as a bundle
  layer_counts(config)      {"sliding", "full", "dense", "sparse"}: layers of
                            a kind
  attention_pairs(config)   (query, key) pairs the masks need, by layer type
  attention_pairs_per_sample(config), attention_heads(config), held_share(config),
  expert_flops_per_assignment(config), expert_train_bytes(config, ...)
                            what the accepted ``flash_roofline`` and
                            ``expert_*`` readers call, for the PR that appends
                            this family's cell to their lists
"""

from __future__ import annotations

from benchmark.families import afmoe_plain
# an expert is the same gated MLP in every decoder family, in the same keys,
# and so are a windowed and a full layer's pairs and the held share
from benchmark.families.mellum_moe import (  # noqa: F401
    attention_heads, attention_pairs, expert_flops_per_assignment,
    expert_train_bytes, held_share,
)
# token ids from ``vocab_size`` (here the vocabulary's slice) with next-token
# targets, and a sample's tokens as its units: as for any language model
from benchmark.families.transformer_lm import (  # noqa: F401
    make_samples, units_per_sample,
)

def build_bundle(config: dict):
    """The program's decoder at the file's sizes.  A program whose decoder
    does not read the file's gate, post-norms, positions by layer kind,
    embedding scale or selection bias would build another model from the
    same file: refused here, before anything is compiled or timed."""
    from fedml_tpu.models.decoder import decoder_lm

    bundle = decoder_lm(config)
    cfg = bundle.module.cfg
    lacks = [name for name, built in (
        ("attn_gate", getattr(cfg, "attn_gate", False)),
        ("post_norm", getattr(cfg, "post_norm", False)),
        ("selection_bias", getattr(cfg, "selection_bias", None) is not None),
        ("embed_scale", getattr(cfg, "embed_scale", 1.0) != 1.0),
        ("rope by layer kind", afmoe_plain.FULL not in dict(cfg.rope)),
    ) if not built]
    if lacks:
        raise ValueError(
            f"this program's decoder does not build {lacks} from the "
            "configuration (fedml_tpu/models/decoder.py), so the cell cannot "
            "run on it")
    return bundle


def plain_bundle(config: dict):
    return afmoe_plain.PlainBundle(config)


def layer_counts(config: dict) -> dict:
    kinds = afmoe_plain.layer_kinds(config)
    return {name: sum(name in kind for pair in kinds for kind in pair)
            for name in ("sliding", "full", "dense", "sparse")}


def attention_pairs_per_sample(config: dict) -> int:
    """Pairs of one sequence through every layer: a windowed layer's query
    at i sees min(i + 1, window) keys, a full layer's i + 1."""
    pairs = attention_pairs(config)
    return sum(pairs[mixer] for mixer, _ in afmoe_plain.layer_kinds(config))


def _dense_products(config: dict) -> list:
    """(in, out) of every dense matmul a token passes, through every layer
    and the head: the fused q/k/v, the gate's and the output projection, the
    dense MLP, the router and the shared expert."""
    h, d = config["hidden_size"], config["head_dim"]
    H, G = config["num_attention_heads"], config["num_key_value_heads"]
    f = config["moe_intermediate_size"] * config["num_shared_experts"]
    wide = config["intermediate_size"]
    n = layer_counts(config)
    mixer = [(h, (H + 2 * G) * d), (h, H * d), (H * d, h)]
    sparse = [(h, config["num_experts_routed"])] + 2 * [(h, f)] + [(f, h)]
    dense = 2 * [(h, wide)] + [(wide, h)]
    return (config["n_layer"] * mixer + n["sparse"] * sparse
            + n["dense"] * dense + [(h, config["vocab_size"])])


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward FLOPs of one token by op class (2 a multiply-add).  ``matmul``
    is exactly the dense products; the routed experts' grouped products have
    a key of their own (they are not in the trace's ``matmul`` class), with
    one held assignment in ``1 / held_share`` in expectation; attention is
    credited with the pairs its masks need, not the whole context."""
    n = layer_counts(config)
    per_pair = 4 * config["head_dim"] * config["num_attention_heads"]
    return {"matmul": 2 * sum(i * o for i, o in _dense_products(config)),
            "expert": (n["sparse"] * held_share(config)
                       * expert_flops_per_assignment(config)),
            "attention": (attention_pairs_per_sample(config) * per_pair
                          / config["n_positions"])}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """Least HBM bytes of the dense matmuls of one training step per token:
    every weight read forward and backward and its gradient written (2
    bytes), over the step's ``batch_units``; activations in and out of each
    matmul once a pass."""
    products = _dense_products(config)
    weights = sum(i * o for i, o in products)
    acts = sum(i + o for i, o in products)
    return {"matmul": 2 * (3 * weights / batch_units + 3 * acts)}
