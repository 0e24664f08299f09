"""The traffic generator: the same seed gives the same inputs, and families
and size rules are found by name."""

import sys
import types

import numpy as np
import pytest

from benchmark import cells, traffic

MANIFEST = cells.manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("cell_name", ["gpt2l_silo_fused", "gpt2l_silo_spmd4"])
def test_the_cells_geometry_is_what_its_why_says(cell_name):
    cell = cells.load_cell(cell_name)
    g = cell.geometry
    sizes = traffic.client_sizes(g, seed=3)
    assert len(sizes) == g["clients"] == g["cohort"] == 4
    assert traffic.steps_per_epoch(g, seed=3) == 4 and g["batch"] == 8
    assert traffic.units_per_sample(cell.config) == 1024
    # a round trains cohort x steps x batch x context tokens, none padded
    assert int(sizes.sum()) * 1024 == 131072


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("rehearsal", [False, True])
def test_every_cells_sizes_follow_the_seed(cell_name, rehearsal):
    g = cells.load_cell(cell_name, rehearsal=rehearsal).geometry
    sizes = traffic.client_sizes(g, seed=3)
    assert (sizes == traffic.client_sizes(g, seed=3)).all()
    assert len(sizes) == g["clients"] >= g["cohort"] >= 1
    assert traffic.steps_per_epoch(g, seed=3) * g["batch"] >= sizes.max()


def test_samples_follow_the_seed_and_the_configuration():
    cell = cells.load_cell("gpt2l_silo_fused", rehearsal=True)
    config = cell.config
    x, y = traffic.make_samples(config, 5, seed=7)
    x2, _ = traffic.make_samples(config, 5, seed=7)
    x3, _ = traffic.make_samples(config, 5, seed=8)
    assert x.shape == (5, config["n_positions"]) and x.dtype == np.int32
    assert (x == x2).all() and (x != x3).any()
    assert x.min() >= 0 and x.max() < config["vocab_size"]
    assert (y[:, :-1] == x[:, 1:]).all()  # next-token targets
    block = traffic.resident_block(config, cell.geometry, 7)
    assert block[0].shape[:3] == block[2].shape == (2, 2, 2)
    assert (block[3] == 4).all() and (block[4] == 1).all()


@pytest.fixture
def thirds(monkeypatch):
    """A size rule that is no file of the benchmark: found by its name."""
    def client_sizes(geometry, rng):
        return rng.integers(1, geometry["sizes"]["most"] + 1,
                            geometry["clients"])

    monkeypatch.setitem(sys.modules, "benchmark.size_rules.thirds",
                        types.SimpleNamespace(client_sizes=client_sizes))
    return {"clients": 50, "cohort": 5, "batch": 4,
            "sizes": {"kind": "thirds", "most": 30}}


def test_a_size_rule_is_found_by_name_and_draws_from_the_seed(thirds):
    a = traffic.client_sizes(thirds, seed=1)
    assert (a == traffic.client_sizes(thirds, seed=1)).all()
    assert (a != traffic.client_sizes(thirds, seed=2)).any()
    assert 1 <= a.min() and a.max() <= 30
    assert traffic.steps_per_epoch(thirds, seed=1) == -(-a.max() // 4)
    with pytest.raises(ModuleNotFoundError):
        traffic.client_sizes({**thirds, "sizes": {"kind": "no_such"}}, 0)


def test_a_resident_block_refuses_unequal_clients(thirds):
    config = cells.load_cell("gpt2l_silo_fused", rehearsal=True).config
    with pytest.raises(ValueError, match="equal size"):
        traffic.resident_block(config, thirds, 0)


def test_a_size_rule_that_gives_the_wrong_number_of_clients_is_refused(
        monkeypatch):
    monkeypatch.setitem(sys.modules, "benchmark.size_rules.short",
                        types.SimpleNamespace(
                            client_sizes=lambda g, rng: np.ones(3, int)))
    with pytest.raises(ValueError, match="for 4 clients"):
        traffic.client_sizes({"clients": 4, "batch": 1,
                              "sizes": {"kind": "short"}}, 0)
