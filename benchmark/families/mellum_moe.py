"""Model family ``mellum_moe``: the configuration-driven decoder of
``fedml_tpu/models/decoder.py`` (RMSNorm, rotary positions, grouped k/v
heads, sliding and full attention layers, a dropless top-k expert layer that
holds a share of the experts, untied head) at the sizes of a configuration
file in the published ``config.json``'s key names; depth is ``n_layer``.

Beside what every family exposes (``families/transformer_lm.py`` lists it):

  plain_bundle(config)      the plain reference's forward pass as a bundle,
                            for a driver to hand ``run.py:check_reference``
  attention_pairs(config)   (query, key) pairs the masks need, by layer type
  attention_pairs_per_sample(config)   the same through every layer
  attention_heads(config)   (q heads, head size) of the flash kernels
  held_share(config)        expected held assignments a token a layer
  expert_flops_per_assignment(config), expert_train_bytes(config, ...)
"""

from __future__ import annotations

from benchmark.families import mellum_moe_plain
# token ids from ``vocab_size`` (here the vocabulary's slice) with next-token
# targets, and a sample's tokens as its units: as for any language model
from benchmark.families.transformer_lm import (  # noqa: F401
    make_samples, units_per_sample,
)


def build_bundle(config: dict):
    from fedml_tpu.models.decoder import decoder_lm

    return decoder_lm(config)


def plain_bundle(config: dict):
    return mellum_moe_plain.PlainBundle(config)


def attention_pairs(config: dict) -> dict:
    """{layer type: (query, key) pairs of one sequence that the layer's mask
    lets through}: a query at i sees min(i + 1, window) keys."""
    L, w = config["n_positions"], min(config["sliding_window"],
                                      config["n_positions"])
    return {"full_attention": L * (L + 1) // 2,
            "sliding_attention": w * (w + 1) // 2 + (L - w) * w}


def attention_pairs_per_sample(config: dict) -> int:
    """Pairs of one sequence through every layer."""
    pairs = attention_pairs(config)
    return sum(pairs[k] for k in mellum_moe_plain.layer_types(config))


def attention_heads(config: dict) -> tuple:
    return config["num_attention_heads"], config["head_dim"]


def held_share(config: dict) -> float:
    """Held token-expert assignments a token a layer, in expectation under a
    uniform router: the published top-k times the share of experts here."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_routed"])


def expert_flops_per_assignment(config: dict) -> int:
    """Forward FLOPs of one token through one expert: gate, up and down."""
    return 3 * 2 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_train_bytes(config: dict, assignments: float,
                       layer_steps: float) -> float:
    """Least HBM bytes of the grouped products of ``layer_steps`` expert
    layers' training steps that made ``assignments`` held assignments: the
    held experts' three matrices read forward and backward and their
    gradients written (2 bytes), and a row of every product in and out once
    a pass."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    weights = config["num_experts"] * 3 * h * f
    rows = (h + f) + (h + f) + (f + h)  # gate, up, down: in + out
    return 2.0 * 3 * (weights * layer_steps + rows * assignments)


def _dense_weights(config: dict) -> int:
    """Parameters of the dense matmuls: q/k/v and output projections and
    the router of every layer, and the head."""
    h, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads"] + config["num_key_value_heads"]
    layer = 2 * h * heads * d + h * config["num_experts_routed"]
    return config["n_layer"] * layer + h * config["vocab_size"]


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward FLOPs of one token by op class (2 a multiply-add).  The
    experts' grouped products have a key of their own: they are not in the
    trace's ``matmul`` class.  Attention is credited with the pairs its
    masks need, not the whole context."""
    per_pair = 4 * config["head_dim"] * config["num_attention_heads"]
    attention = (attention_pairs_per_sample(config) * per_pair
                 / config["n_positions"])
    expert = (config["n_layer"] * held_share(config)
              * expert_flops_per_assignment(config))
    return {"matmul": 2 * _dense_weights(config), "expert": expert,
            "attention": attention}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """Least HBM bytes of the dense matmuls of one training step per token:
    every weight read forward and backward and its gradient written (2
    bytes), over the step's ``batch_units``; activations in and out of each
    matmul once a pass."""
    h, d = config["hidden_size"], config["head_dim"]
    qkv = (config["num_attention_heads"]
           + 2 * config["num_key_value_heads"]) * d
    acts = config["n_layer"] * (
        h + qkv + config["num_attention_heads"] * d + h
        + h + config["num_experts_routed"]) + h + config["vocab_size"]
    return {"matmul": 2 * (3 * _dense_weights(config) / batch_units
                           + 3 * acts)}
