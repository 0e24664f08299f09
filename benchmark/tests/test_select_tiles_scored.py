"""``sparse_select_tiles_scored_pct`` (PR 40): the share of a sequence's 16 x
16 tiles whose index scores the choice computed, from the program's counter
alone, with a stand-in session: no trace is read."""

import types

import numpy as np
import pytest

from benchmark import cells, trace_reduce

NAME = "sparse_select_tiles_scored_pct"
CELL = "keyevl2_silo_text8k"
LAYERS, SIDE = 4, 16  # the cell: 4 layers, 8192 positions in tiles of 512


def context(metrics, calls=3, samples=16):
    call = (0.0, 0.1, 1, {k: np.array([v]) for k, v in metrics.items()})
    return trace_reduce.Context(
        summary=None, cell=cells.load_cell(CELL),
        session=types.SimpleNamespace(
            padded_samples_per_round=lambda: samples),
        calls=[call] * calls, device_kind="TPU v5 lite")


def read(ctx):
    return cells.load_layer_metric(NAME).read(ctx)


def test_the_manifest_reads_it_in_this_cell_only():
    entry = {m["name"]: m for m in cells.manifest()["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "tokens_per_s", "workloads": [CELL]}
    assert cells.manifest()["per_layer"][-1]["name"] == NAME  # appended


def test_the_counter_a_reader_names_is_the_programs():
    from fedml_tpu.models.decoder import SELECT_TILES_SCORED

    assert cells.load_layer_metric(NAME).COUNTER == SELECT_TILES_SCORED
    config = cells.load_cell(CELL).config
    assert (config["n_layer"], config["n_positions"] // 512) == (LAYERS, SIDE)


@pytest.mark.parametrize("tiles, want", [
    pytest.param(SIDE * (SIDE + 1) // 2, 53.125, id="the_kernels_count"),
    pytest.param(SIDE * SIDE, 100.0, id="the_lax_forms_count"),
    pytest.param(SIDE, 6.25, id="a_tile_a_row_block"),
])
@pytest.mark.parametrize("calls, samples", [(3, 16), (1, 4)])
def test_it_is_the_counter_over_every_tile(tiles, want, calls, samples):
    # a call: one round of `samples` sequences through 4 layers
    got = read(context({"count": 8192.0 * samples,
                        "select_tiles_scored": float(
                            tiles * LAYERS * samples)},
                       calls=calls, samples=samples))
    assert got == pytest.approx(want)


def test_nothing_without_the_counter():
    assert read(context({"count": 8192.0, "attn_tiles_live": 544.0})) is None
    assert read(context({"select_tiles_scored": 1.0}, calls=0)) is None
