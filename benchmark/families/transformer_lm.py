"""Model family ``transformer_lm``: the decoder of ``models/transformer.py``
(pre-LN, learned positions, GELU MLP, weight-tied head) at the sizes of a
configuration file in GPT-2's key names.

A family is found by the ``family`` key of a configuration file.  Its module
exposes what the harness needs to know of a kind of model, and nothing else
in the harness knows a family by name:

  build_bundle(config)        the program's own ModelBundle at the file's sizes
  make_samples(config, n, rng)  (x [n, ...], y [n, ...]) host arrays of inputs
  units_per_sample(config)    what the throughput metric counts in one sample
  fwd_flops_per_unit(config)  {op class: forward FLOPs of one unit}
  train_bytes_per_unit(config, batch_units)  {op class: least HBM bytes}

and, where its attention runs in the flash kernels, what ``flash_roofline``
asks for: ``attention_pairs_per_sample(config)``, ``attention_heads(config)``.
"""

from __future__ import annotations

import numpy as np


def build_bundle(config: dict):
    from fedml_tpu.models.transformer import transformer_lm

    return transformer_lm(
        vocab_size=config["vocab_size"], embed_dim=config["n_embd"],
        num_heads=config["n_head"], num_layers=config["n_layer"],
        seq_len=config["n_positions"], remat=config["remat"])


def make_samples(config: dict, n: int, rng: np.random.Generator):
    """Token ids with next-token targets."""
    x = rng.integers(0, config["vocab_size"], (n, config["n_positions"]),
                     dtype=np.int32)
    return x, np.roll(x, -1, axis=-1)


def units_per_sample(config: dict) -> int:
    """A sample is one context: the metric counts its tokens."""
    return config["n_positions"]


def attention_pairs_per_sample(config: dict) -> int:
    """(query, key) pairs of one sequence that the causal mask lets through,
    over every layer: a query at i sees i + 1 keys."""
    L = config["n_positions"]
    return config["n_layer"] * (L * (L + 1) // 2)


def attention_heads(config: dict) -> tuple:
    """(q heads, head size)."""
    return config["n_head"], config["n_embd"] // config["n_head"]


def fwd_flops_per_unit(config: dict) -> dict:
    """Forward matmul FLOPs of one token, by op class (2 FLOPs a
    multiply-add).  An embedding lookup is free and the weight-tied head is a
    [*, d] @ [d, V] matmul: ``bench.py:build_fedllm``'s accounting."""
    d, L = config["n_embd"], config["n_positions"]
    inner = config.get("n_inner") or 4 * d
    dense = config["n_layer"] * 2 * (4 * d * d + 2 * d * inner)  # qkv+proj, mlp
    head = 2 * d * config["vocab_size"]
    # scores + values over the whole context, as the blockwise kernel
    # computes them (the causal half is not skipped)
    attention = config["n_layer"] * 4 * L * d
    return {"matmul": dense + head, "attention": attention}


def train_bytes_per_unit(config: dict, batch_units: int) -> dict:
    """HBM bytes the dense matmuls of one training step must move per token,
    at the least: every weight read once forward and once backward and its
    gradient written once (compute dtype, 2 bytes), amortised over the
    step's ``batch_units``; activations in and out of each matmul once per
    pass.  Used only to say which side of the roofline bounds a class."""
    d = config["n_embd"]
    inner = config.get("n_inner") or 4 * d
    weights = config["n_layer"] * (4 * d * d + 2 * d * inner) \
        + d * config["vocab_size"]
    acts = config["n_layer"] * (2 * d + 3 * d + 2 * d + 2 * inner + 2 * d) \
        + d + config["vocab_size"]
    return {"matmul": 2 * (3 * weights / batch_units + 3 * acts)}
