"""``bert_attention_ms_per_step``: the reader on the program's summary of a
traced run of ``bert-large.dp1`` on the chip, what it gives where there is
nothing to read (an untraced run, the parent's model that names no part, a
program without the reducer), and its entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark import manifest

METRIC = "bert_attention_ms_per_step"
CELLS = ["bert-large.dp1", "bert-large.dp4"]


@pytest.fixture()
def recorded(monkeypatch):
    """The program's summary of the cell's traced run on the chip (PR 51),
    cut to what the readers take."""
    from bagua_tpu.observability import trace_analysis

    with open(os.path.join(manifest.HERE, "testdata", "bert-large.dp1.summary.json")) as f:
        summary = json.load(f)
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", summary)
    return {"trace": {"busy_s": 1.0}, "peaks": manifest.peaks("TPU v5 lite"), "batch_per_chip": 32}


def test_the_reader_adds_up_the_projections_and_the_core(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    read = manifest.layer_metric_reader(METRIC)(recorded)
    assert read == pytest.approx(parts["attn_proj"] + parts["attn_core"])
    # the model names these two parts and no other: the rest of a layer is ``other``
    assert set(parts) == {"attn_proj", "attn_core", "other"}
    classes = summary["partition_ms"]
    assert sum(parts.values()) == pytest.approx(classes["forward"] + classes["backward"], rel=1e-9)
    # 24 layers' attention is a part of the step and not most of it
    assert 0.1 * summary["step_busy_ms"] < read < 0.5 * summary["step_busy_ms"]


@pytest.mark.parametrize("missing", ["trace", "parts", "reducer"])
def test_the_reader_gives_none_where_there_is_nothing_to_read(missing, recorded, monkeypatch):
    from bagua_tpu.observability import trace_analysis

    read = manifest.layer_metric_reader(METRIC)
    assert read(recorded) > 0
    if missing == "trace":
        assert read({**recorded, "trace": None}) is None
    elif missing == "parts":  # a program whose model names no part (the parent's)
        plain = {k: v for k, v in trace_analysis.last_summary().items() if k != "model_part_ms"}
        monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", plain)
        assert read(recorded) is None
    else:
        monkeypatch.delattr(trace_analysis, "last_summary")
        assert read(recorded) is None


@pytest.mark.parametrize("cell", CELLS)
def test_both_bert_cells_report_it_and_no_other_cell_does(cell):
    bench = manifest.benchmark_json()
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry == {"name": METRIC, "unit": "ms", "better": "lower", "source": "program_span",
                     "layer": "attention", "moves": "samples_per_s_per_chip", "workloads": CELLS}
    assert METRIC in [m["name"] for m in manifest.load_cell(cell).per_layer]
    others = [w["name"] for w in bench["workloads"] if w["name"] not in CELLS]
    assert all(METRIC not in [m["name"] for m in manifest.load_cell(name).per_layer]
               for name in others)
