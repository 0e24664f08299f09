"""Model family ``nemotron_h``: the configuration-driven decoder of
``fedml_tpu/models/decoder.py`` as a hybrid whose layers each hold one
sublayer, read from ``hybrid_override_pattern``: Mamba-2 state-space mixers
(``M``), causal softmax attention with no positions (``*``) and a latent
mixture of squared-ReLU experts behind a sigmoid router with a frozen
selection bias, beside a squared-ReLU shared expert (``E``); the routed
experts work in ``moe_latent_size`` between a down- and an up-projection.
At the sizes of a configuration file in the published ``config.json``'s key
names; depth is ``n_layer``.

Beside what every family exposes (``families/transformer_lm.py`` lists it):

  plain_bundle(config)      the plain reference's forward pass as a bundle
  layer_counts(config)      {"mamba", "attention", "sparse", "dense"}: layers
                            of a kind
  ssm_flops_per_token(config)   the recurrence's own forward FLOPs of one
                            token through one Mamba layer, every head
  ssm_bytes_per_token(config)   its least HBM bytes a pass
  attention_pairs_per_sample(config), attention_heads(config), held_share(config),
  expert_flops_per_assignment(config)
"""

from __future__ import annotations

from benchmark.families import nemotron_h_plain
# token ids from ``vocab_size`` (here the vocabulary's slice) with next-token
# targets, and a sample's tokens as its units: as for any language model
from benchmark.families.transformer_lm import (  # noqa: F401
    make_samples, units_per_sample,
)


def build_bundle(config: dict):
    """The program's decoder at the file's sizes.  A program whose decoder
    does not read the file's pattern, Mamba mixers, squared-ReLU experts,
    latent width or shared expert's width would build another model from the
    same file: refused here, before anything is compiled or timed."""
    from fedml_tpu.models.decoder import decoder_lm

    bundle = decoder_lm(config)
    cfg = bundle.module.cfg
    kinds = [mixer or mlp for mixer, mlp in nemotron_h_plain.layer_kinds(
        config)]
    lacks = [name for name, built in (
        ("Mamba-2 mixers", getattr(cfg, "ssm_heads", 0)
         == config["mamba_num_heads"]),
        ("one sublayer a layer", cfg.layer_types.count("none")
         == kinds.count("sparse") + kinds.count("dense")),
        ("relu2 experts", getattr(cfg, "mlp_act", None) == "relu2"),
        ("latent experts", getattr(cfg, "moe_latent_size", 0)
         == config["moe_latent_size"]),
        ("the shared expert's width", cfg.shared_expert_size
         == config["moe_shared_expert_intermediate_size"]),
        ("the router's width", cfg.num_experts_routed
         == config["n_routed_experts"]),
    ) if not built]
    if lacks:
        raise ValueError(
            f"this program's decoder does not build {lacks} from the "
            "configuration (fedml_tpu/models/decoder.py), so the cell cannot "
            "run on it")
    return bundle


def plain_bundle(config: dict):
    return nemotron_h_plain.PlainBundle(config)


def layer_counts(config: dict) -> dict:
    kinds = nemotron_h_plain.layer_kinds(config)
    return {name: sum(name in kind for kind in kinds)
            for name in ("mamba", "attention", "sparse", "dense")}


def _ssm_shape(config: dict) -> tuple:
    """(heads here, head size, groups, state size)."""
    return (config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"])


def ssm_flops_per_token(config: dict) -> int:
    """Forward FLOPs of the recurrence itself for one token through one
    Mamba layer: a head's decay of its [N, P] state (a multiply), the update
    ``dt B x^T`` and the read ``S^T C`` (a multiply-add each), 5 N P.  What
    a chunked form computes besides earns nothing."""
    H, P, _, N = _ssm_shape(config)
    return H * 5 * N * P


def ssm_bytes_per_token(config: dict) -> int:
    """Least HBM bytes of one pass over one token of one Mamba layer: x, B
    and C in and y out once, 2 bytes each, and dt in, float32."""
    H, P, G, N = _ssm_shape(config)
    return 2 * (2 * H * P + 2 * G * N) + 4 * H


def attention_pairs_per_sample(config: dict) -> int:
    """(query, key) pairs of one sequence that the causal mask lets through,
    over every attention layer: a query at i sees i + 1 keys."""
    L = config["n_positions"]
    return layer_counts(config)["attention"] * (L * (L + 1) // 2)


def attention_heads(config: dict) -> tuple:
    return config["num_attention_heads"], config["head_dim"]


def held_share(config: dict) -> float:
    """Held token-expert assignments a token an expert layer, in expectation
    under a uniform router: the published top-k times the share of experts
    here."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["n_routed_experts"])


def expert_flops_per_assignment(config: dict) -> int:
    """Forward FLOPs of one token through one expert in the latent width: up
    and down."""
    return 2 * 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def _dense_products(config: dict) -> list:
    """(in, out) of every dense matmul a token passes, through every layer
    and the head: a Mamba layer's in_proj and out_proj, an attention layer's
    fused q/k/v and output, an expert layer's router, latent projections and
    shared expert, a dense layer's MLP."""
    h = config["hidden_size"]
    H, P, G, N = _ssm_shape(config)
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, latent = config["head_dim"], config["moe_latent_size"]
    shared = config["moe_shared_expert_intermediate_size"]
    wide = config["intermediate_size"]
    n = layer_counts(config)
    mamba = [(h, 2 * H * P + 2 * G * N + H), (H * P, h)]
    attention = [(h, (Hq + 2 * Hkv) * d), (Hq * d, h)]
    sparse = [(h, config["n_routed_experts"]), (h, latent), (latent, h),
              (h, shared), (shared, h)]
    dense = [(h, wide), (wide, h)]
    return (n["mamba"] * mamba + n["attention"] * attention
            + n["sparse"] * sparse + n["dense"] * dense
            + [(h, config["vocab_size"])])


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward FLOPs of one token by op class (2 a multiply-add).  ``matmul``
    is exactly the dense products; the routed experts' grouped products (one
    held assignment in ``1 / held_share`` in expectation), attention (the
    causal pairs) and the recurrence (its own count, not the chunked form's)
    have keys of their own.  The chunked scan's inner products are XLA dots
    whose seconds land in the trace's ``matmul`` class while their FLOPs do
    not, so ``matmul_roofline`` reads low here."""
    n = layer_counts(config)
    per_pair = 4 * config["head_dim"] * config["num_attention_heads"]
    return {"matmul": 2 * sum(i * o for i, o in _dense_products(config)),
            "expert": (n["sparse"] * held_share(config)
                       * expert_flops_per_assignment(config)),
            "attention": (attention_pairs_per_sample(config) * per_pair
                          / config["n_positions"]),
            "ssm": n["mamba"] * ssm_flops_per_token(config)}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """Least HBM bytes of the dense matmuls of one training step per token:
    every weight read forward and backward and its gradient written (2
    bytes), over the step's ``batch_units``; activations in and out of each
    matmul once a pass."""
    products = _dense_products(config)
    weights = sum(i * o for i, o in products)
    acts = sum(i + o for i, o in products)
    return {"matmul": 2 * (3 * weights / batch_units + 3 * acts)}
