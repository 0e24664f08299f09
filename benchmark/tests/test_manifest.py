"""Manifest lint: ``BENCHMARK.json`` against the contract's limits, and every
file it names by name exists and loads."""

import json
import os
import re

import pytest

from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = cells.manifest()
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(cells.REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert all(one_line(w) for w in MANIFEST["command"])
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    n = len(MANIFEST["workloads"])
    # the whole check must fit with the full 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) \
        + 24 * 2 * 90 + 1200 <= 43200
    assert 2 <= n <= 24 and 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in MANIFEST["per_layer"]
    keys = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) - {"workloads"} == keys
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    in_cells = metric.get("workloads", CELLS)
    assert in_cells and set(in_cells) <= set(CELLS)
    if per_layer:
        assert one_line(metric["layer"])
        moved = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert moved, f"{metric['name']} moves no end-to-end metric"
        # reported only where the metric it moves is
        assert set(in_cells) <= set(moved[0].get("workloads", CELLS))
        assert os.path.exists(os.path.join(
            cells.ROOT, "layer_metrics", f"{metric['name']}.py"))
        assert callable(cells.load_layer_metric(metric["name"]).read)
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    (setup,) = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_files_exist_and_load(cell_name):
    (entry,) = [w for w in MANIFEST["workloads"] if w["name"] == cell_name]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert one_line(entry["why"]) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4)
    cell = cells.load_cell(cell_name)
    assert cell.workload["config"] == entry["config"]
    assert cell.workload["traffic"] == entry["traffic"]
    assert cell.workload["chips"] == entry["chips"]
    assert cell.workload["why"] == entry["why"]
    assert callable(cells.load_driver(cell.workload["driver"]).Session)
    family = cells.load_family(cell.config)
    for part in ("build_bundle", "make_samples", "units_per_sample",
                 "fwd_flops_per_unit", "train_bytes_per_unit"):
        assert callable(getattr(family, part)), part
    assert callable(cells.load_size_rule(cell.geometry).client_sizes)
    assert cells.load_cell(cell_name, rehearsal=True).geometry
    others = [m for m in MANIFEST["end_to_end"] if m["name"] != "setup_s"
              and cell_name in m.get("workloads", CELLS)]
    layers = [m for m in MANIFEST["per_layer"]
              if cell_name in m.get("workloads", CELLS)]
    assert others and layers


def test_pairs_of_config_and_traffic_appear_once():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


WIDTH = re.compile(r"(hidden|inner|intermediate|latent|state|proj|_dim$|_rank$"
                   r"|head_size|n_embd|expansion|per_tok)")


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert len(config["reduced"]) <= 16
    assert not [k for k in config["reduced"] if WIDTH.search(k)]
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    with open(os.path.join(cells.REPO, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    # what the file says it changed is what the manifest says
    assert sorted(body["published"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        assert body[key] != body["published"][key]
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)


def test_no_benchmark_file_has_a_character_outside_a_name():
    for path in MANIFEST["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), cells.REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
