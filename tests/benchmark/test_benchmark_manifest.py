"""``BENCHMARK.json`` against the contract it is written to, and the data
files it names: every cell's, configuration's, mix's and metric's files are
found by name."""

import json
import os
import re

import pytest

from benchmark import manifest

BENCH = manifest.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16 and all(PATH.match(p) for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32 and all(one_line(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43200 seconds
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(BENCH["configs"]) <= 24 and 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [entry["name"] for entry in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_configuration_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and one_line(entry["source"]) and one_line(entry["why"])
    assert len(entry["reduced"]) <= 16 and all(NAME.match(k) for k in entry["reduced"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    config = manifest.load_json(entry["file"])
    assert config["name"] == entry["name"] and config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    for key in ("adapter", "reference"):
        assert os.path.isfile(os.path.join(manifest.ROOT, config[key]))
    for key in ("optimizer", "precision", "toy", "assumed", "departures"):
        assert key in config


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry_and_its_files_are_found_by_name(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
    assert entry["chips"] in (1, 4) and one_line(entry["why"])
    for dry in (False, True):
        cell = manifest.load_cell(name, dry=dry)
        assert cell.chips == entry["chips"] == cell.traffic["chips"]
        for fn in ("sizes", "build_loss", "as_stored", "to_program", "draw_batch",
                   "train_flops_per_sample"):
            assert callable(getattr(cell.adapter, fn))
        assert callable(cell.reference.init_params) and callable(cell.reference.loss)
        assert cell.global_batch == cell.traffic["batch_per_chip"] * cell.chips
    detail = manifest.load_json("benchmark", "workloads", name + ".json")
    assert detail["why"] == entry["why"]
    # each number compared has a limit of its own, with the readings it was set from
    for tolerances in (detail["tolerances"], detail["toy_tolerances"]):
        for number, record in tolerances.items():
            assert number.startswith("_") or "limit" in record, number


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric(name):
    cell = manifest.load_cell(name)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert one_line(metric["layer"])
        assert callable(manifest.layer_metric_reader(metric["name"]))


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_that_each_reporting_cell_reports(metric):
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1
    assert set(metric.get("workloads", CELLS)) <= set(moved[0].get("workloads", CELLS))


def test_setup_has_the_bound_the_contract_gives_it():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and setup["unit"] == "s" and setup["better"] == "lower"


def test_the_four_chip_share_is_within_the_limit():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_files_under_the_paths_are_named_from_a_names_characters():
    for top in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(manifest.ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), manifest.ROOT)
                assert PATH.match(rel), rel


def test_peaks_are_keyed_by_device_kind_and_refuse_an_unknown_one():
    v5e = manifest.peaks("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9}
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            manifest.peaks(kind)
