"""Model family ``keye_sparse``: the configuration-driven decoder of
``fedml_tpu/models/decoder.py`` with every layer's attention over the keys a
learned indexer picks (kind ``sparse_attention``: ``sa_config``'s ``topk`` of
``sum_j w_j relu(qI_j . kI)``, q and k heads normed, rotary positions, grouped
k/v heads) and a dropless top-k expert layer that holds a share of the
experts, at the sizes of a configuration file in the published
``config.json``'s key names; depth is ``n_layer``.

Beside what every family exposes (``families/transformer_lm.py`` lists it):

  plain_bundle(config)      the plain reference's forward pass as a bundle,
                            for a driver to hand ``run.py:check_reference``
  chosen_pairs(config)      (query, key) pairs of one sequence and layer that
                            the choice keeps: a query at i sees min(i + 1,
                            topk) keys
  causal_pairs_per_sample(config)     pairs the indexer scores, every layer
  attention_pairs_per_sample(config)  the **chosen** pairs, every layer
  attention_heads(config)   (q heads, head size) of the attention kernels
  index_flops_per_pair(config)        2 x index heads x their size
  index_bytes_per_token(config)       qI, kI, w read and a row of the choice
                                      written, once
  held_share(config), expert_flops_per_assignment(config),
  expert_train_bytes(config, ...)     as ``families/mellum_moe.py``

**The indexer is credited under no key of ``fwd_flops_per_unit``**: nothing
differentiates it, it runs forward only, and ``flops.train_flops_per_unit``
counts every key three times.  Its projections' seconds land in the trace's
``matmul`` class, so ``matmul_roofline`` and ``step_mfu_pct`` read a little
low in this family's cells: the safe side.
"""

from __future__ import annotations

from benchmark.families import keye_sparse_plain
# the expert layer's and the credited dense products' counts are that
# family's: the same layer, the same fused q/k/v, output, router and head
from benchmark.families.mellum_moe import (  # noqa: F401
    _dense_weights, expert_flops_per_assignment, expert_train_bytes,
    held_share, train_bytes_per_unit,
)
# token ids from ``vocab_size`` (here the vocabulary's slice) with next-token
# targets, and a sample's tokens as its units: as for any language model
from benchmark.families.transformer_lm import (  # noqa: F401
    make_samples, units_per_sample,
)


KIND = "sparse_attention"  # the program's name for a layer with an indexer


def build_bundle(config: dict):
    """The program's decoder at the file's sizes.  A program whose decoder
    does not read ``sa_config`` would build full-attention layers from the
    same file: refused here, before anything is compiled or timed."""
    from fedml_tpu.models.decoder import decoder_lm

    bundle = decoder_lm(config)
    kinds = set(bundle.module.cfg.layer_types)
    if kinds != {KIND}:
        raise ValueError(
            f"this program's decoder builds {sorted(kinds)} layers from a "
            f"configuration with sa_config: it has no {KIND!r} kind "
            "(fedml_tpu/models/decoder.py), so the cell cannot run on it")
    return bundle


def plain_bundle(config: dict):
    return keye_sparse_plain.PlainBundle(config)


def chosen_pairs(config: dict) -> int:
    """Pairs of one sequence that one layer's choice keeps."""
    L = config["n_positions"]
    k = min(config["sa_config"]["topk"], L)
    return k * (k + 1) // 2 + (L - k) * k


def causal_pairs_per_sample(config: dict) -> int:
    """Pairs the index scores cover, through every layer."""
    L = config["n_positions"]
    return config["n_layer"] * (L * (L + 1) // 2)


def attention_pairs_per_sample(config: dict) -> int:
    """Chosen pairs of one sequence through every layer: what the attention
    over them needs, whatever a masked tile computes and drops."""
    return config["n_layer"] * chosen_pairs(config)


def attention_heads(config: dict) -> tuple:
    return config["num_attention_heads"], config["head_dim"]


def index_flops_per_pair(config: dict) -> int:
    """One (query, key) pair's index score: a product a head."""
    sa = config["sa_config"]
    return 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def index_bytes_per_token(config: dict) -> int:
    """Least HBM bytes of one token's choice in one layer: ``qI``, ``kI``
    and ``w`` read in float32, a row of the [L, L] int8 mask written."""
    sa = config["sa_config"]
    floats = (sa["indexer_num_heads"] + 1) * sa["indexer_head_dim"] \
        + sa["indexer_num_heads"]
    return 4 * floats + config["n_positions"]


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward FLOPs of one token by op class (2 a multiply-add).  Attention
    is credited with the chosen pairs only, the experts with one held
    assignment a token a layer in expectation; the indexer with nothing."""
    per_pair = 4 * config["head_dim"] * config["num_attention_heads"]
    attention = (attention_pairs_per_sample(config) * per_pair
                 / config["n_positions"])
    expert = (config["n_layer"] * held_share(config)
              * expert_flops_per_assignment(config))
    return {"matmul": 2 * _dense_weights(config), "expert": expert,
            "attention": attention}
