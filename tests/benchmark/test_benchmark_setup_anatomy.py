"""The set-up readers over the program's record of cold events: each gives
its number on a made-up record and context, None without ``since``, and None
on a program that has no record (the parent of the PR that added it); their
entries in ``BENCHMARK.json``; and all seven in a traced dry run's line."""

import collections
import json
import os
import subprocess
import sys

import pytest

from bagua_tpu.observability import cold_start
from bagua_tpu.observability.cold_start import ColdEvent
from benchmark import manifest, setup_anatomy
from test_benchmark_run import BENCH, later_pr

GROUP, TRAINER, INIT_STATE = cold_start.INIT_SPANS
BUILD, DISPATCH, TEXT = cold_start.STEP_SPANS
SINCE = 100.0
SETUP_S = 50.0  # the run started at 50 on the record's clock


def made_up_record():
    """Imports [60, 62]; group, trainer and ``init_state`` of 0.5 s each, with
    a program of 0.25 s compiled inside the last; five makers of the
    caller's at 1 s each, two of them written to the cache; the step built
    under the text span: trace 8, lowering 2, compile 4 in a span of 15; a
    program compiled after the window began."""
    e = ColdEvent
    text, build = (TEXT, "default"), (BUILD, "default")
    events = [
        e(cold_start.IMPORT_SPAN, 60.0, 61.5, "bagua_tpu", None),
        e(cold_start.IMPORT_SPAN, 61.5, 62.0, "bagua_tpu.trainer", None),
        e(GROUP, 62.0, 62.5, None, None),
        e(TRAINER, 62.5, 63.0, None, None),
        e(cold_start.BACKEND_COMPILE_EVENT, 63.25, 63.5, "jit(_rest_of_state)", (INIT_STATE, None)),
        e(INIT_STATE, 63.0, 63.5, None, None),
    ]
    for k in range(5):
        events.append(e(cold_start.BACKEND_COMPILE_EVENT, 64.0 + k, 65.0 + k, "jit(<lambda>)", None))
    events += [
        e(cold_start.CACHE_MISS_EVENT, 65.0, 65.0, None, None),
        e(cold_start.CACHE_MISS_EVENT, 66.0, 66.0, None, None),
        e(cold_start.TRACE_EVENT, 70.0, 78.0, "local_step", text),
        e(cold_start.LOWERING_EVENT, 78.0, 80.0, "jit(local_step)", text),
        e(cold_start.BACKEND_COMPILE_EVENT, 80.0, 84.0, "jit(local_step)", text),
        e(TEXT, 70.0, 85.0, "default", build),
        e(BUILD, 69.5, 85.0, "default", None),
        e(DISPATCH, 85.0, 86.0, "default", None),
        e(cold_start.BACKEND_COMPILE_EVENT, 110.0, 117.0, "jit(reference)", None),
        e(cold_start.CACHE_MISS_EVENT, 117.0, 117.0, None, None),
    ]
    return events


EXPECTED = {
    "setup_import_s": 2.0,
    "setup_init_s": 1.25,
    "setup_step_trace_s": 10.0,
    "setup_step_compile_s": 4.0,
    "setup_other_programs_s": 5.25,
    "setup_cache_misses": 2.0,
    "setup_named_pct": 100.0 * (2.0 + 1.25 + 10.0 + 4.0 + 1.0 + 5.25) / SETUP_S,
}


def context(since=SINCE):
    counters = {"dispatch_ms_per_step": 1.5, "steps": 300, "step_wall_ms": {}}
    if since is not None:
        counters["since"] = since
    return {"trace": {"steps": 6}, "end_to_end": {"setup_s": SETUP_S},
            "counters": {"compiles_in_window": 0, "host_overhead": counters}}


@pytest.fixture()
def recorded(monkeypatch):
    monkeypatch.setattr(cold_start, "_record", collections.deque(made_up_record()))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_its_number(name, recorded):
    assert manifest.layer_metric_reader(name)(context()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_without_since(name, recorded):
    assert manifest.layer_metric_reader(name)(context(since=None)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_on_a_program_without_the_record(name, monkeypatch):
    # what ``import`` finds on the parent: no such module
    monkeypatch.setitem(sys.modules, "bagua_tpu.observability.cold_start", None)
    assert manifest.layer_metric_reader(name)(context()) is None


def test_the_named_share_is_the_classes_over_the_runs_own_setup_s(recorded):
    found = setup_anatomy.partition(context())
    assert sum(found[key] for key in setup_anatomy.CLASSES) == pytest.approx(23.5)
    assert found["step_text"] == pytest.approx(1.0)  # the text span less the step made inside it
    assert found["other_programs_count"] == 6  # the reference's, after the window began, is not there
    assert manifest.layer_metric_reader("setup_named_pct")(context()) <= 100.0


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_every_new_metric_has_its_file_and_its_entry_after_the_accepted_ones(bench):
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    # found by name: the seven keep the order their PR gave them (``EXPECTED``'s) and stand
    # after the entries accepted before them; what later PRs append comes after and is theirs
    assert [name for name in names if name in EXPECTED] == list(EXPECTED)
    assert names.index("st_moe_experts_roofline_pct") < names.index("setup_import_s")
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for entry in entries:
        if entry["name"] not in EXPECTED:
            continue
        assert entry["moves"] == "setup_s" and entry["moves"] in end_to_end
        assert "workloads" not in entry and entry["layer"] in ("entry", "engine")
        assert entry["source"] in ("program_span", "program_counter")
        assert os.path.exists(os.path.join(
            manifest.ROOT, "benchmark", "layer_metrics", entry["name"] + ".py"))
    # ... and every cell reports them: each reports setup_s
    for workload in BENCH["workloads"]:
        assert set(EXPECTED) <= {m["name"] for m in manifest.load_cell(workload["name"]).per_layer}


def test_a_traced_dry_run_prints_all_seven_and_names_at_most_the_whole():
    bench = manifest.benchmark_json()
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, *bench["command"][1].split("/")),
         "--workload", "bert-large.dp1", "--seed", str(2**31 + 54321), "--seconds", "2",
         "--trace", "1", "--dry-run"],
        capture_output=True, text=True, timeout=900, cwd=manifest.ROOT)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    assert set(EXPECTED) <= set(metrics)
    assert all(metrics[name]["value"] >= 0 for name in EXPECTED)
    assert 0 < metrics["setup_named_pct"]["value"] <= 100
    for name in ("setup_import_s", "setup_step_trace_s", "setup_step_compile_s",
                 "setup_other_programs_s"):
        assert metrics[name]["value"] > 0, name
    assert metrics["setup_cache_misses"]["unit"] == "count"
