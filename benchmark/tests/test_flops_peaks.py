"""The yardstick's arithmetic: FLOP counts from the configuration files'
shapes, and the peaks table."""

import pytest

from benchmark import cells, flops, peaks


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("kw", [
    dict(vocab=8192, embed_dim=1280, num_heads=10, num_layers=12,
         seq_len=1024),  # bench.py's own defaults
    dict(vocab=50257, embed_dim=1280, num_heads=20, num_layers=36,
         seq_len=1024),  # gpt2-large as published
    dict(vocab=64, embed_dim=32, num_heads=2, num_layers=1, seq_len=16),
])
def test_gpt2_count_equals_bench_py_formula(kw):
    # bench.py:build_fedllm's accounting, written out at its arguments
    # (building its model to read the number back would compile nothing
    # but allocates the weights)
    per_token_fwd = (kw["num_layers"] * (2 * 12 * kw["embed_dim"] ** 2
                                         + 4 * kw["seq_len"] * kw["embed_dim"])
                     + 2 * kw["embed_dim"] * kw["vocab"])
    config = {"family": "transformer_lm", "n_embd": kw["embed_dim"],
              "n_layer": kw["num_layers"], "n_positions": kw["seq_len"],
              "vocab_size": kw["vocab"], "n_inner": 4 * kw["embed_dim"]}
    assert sum(flops.train_flops_per_unit(config).values()) \
        == 3 * per_token_fwd


def test_gpt2_formula_matches_bench_py_itself_at_a_toy_size():
    import bench

    kw = dict(vocab=64, embed_dim=32, num_heads=2, num_layers=1, seq_len=16)
    *_, flops_per_token = bench.build_fedllm(clients=1, batch=1, steps=1,
                                             **kw)
    config = {"family": "transformer_lm", "n_embd": 32, "n_layer": 1,
              "n_positions": 16, "vocab_size": 64}
    assert sum(flops.train_flops_per_unit(config).values()) == flops_per_token


def test_a_family_is_found_by_the_configurations_key(monkeypatch):
    import sys
    import types

    monkeypatch.setitem(sys.modules, "benchmark.families.two_matmuls",
                        types.SimpleNamespace(
                            fwd_flops_per_unit=lambda c: {"matmul": c["n"]},
                            train_bytes_per_unit=lambda c, b: {}))
    config = {"family": "two_matmuls", "n": 7}
    assert flops.train_flops_per_unit(config) == {"matmul": 21}
    assert flops.train_bytes_per_unit(config, 8) == {}
    with pytest.raises(ModuleNotFoundError):
        flops.train_flops_per_unit({"family": "no_such_family"})


def test_bytes_of_the_dense_matmuls_say_flops_bound_them_at_the_cells_batch():
    config = cells.read_json("configs", "gpt2-large.json")
    pk = peaks.peaks("TPU v5 lite")
    f = flops.train_flops_per_unit(config)["matmul"]
    b = flops.train_bytes_per_unit(config, 8 * 1024)["matmul"]
    assert f / pk["bf16_flops_per_s"] > b / pk["hbm_bytes_per_s"]
