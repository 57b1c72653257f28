"""The per-layer readers over the program's own summary of a capture and
over its host counters: each gives its number on a made-up context, and
None without a trace, without a summary, and on a program that has neither
the reducer nor the counters (the parent of the PR that added them)."""

import pytest

from bagua_tpu.observability import trace_analysis
from benchmark import manifest

SUMMARY = {
    "labeled": True,
    "step_busy_ms": 80.0,
    "partition_ms": {"forward": 19.0, "backward": 38.0, "optimizer": 2.0, "exchange": 15.0,
                     "restack": 2.0, "unattributed": 4.0},
    "exchange": {"calls": 8.0, "bytes": 860_000_000, "collective_ms": 15.0, "exposed_ms": 15.0,
                 "tail_ms": 2.3, "ops": []},
}
COUNTERS = {"pre_ms_per_step": 0.1, "lock_wait_ms_per_step": 0.0, "dispatch_ms_per_step": 1.5,
            "post_ms_per_step": 0.1, "build_ms_per_step": 0.0, "telemetry_ms_per_step": 0.25,
            "health_ms_per_step": 0.125, "next_batch_ms_per_step": 58.0, "loop_ms_per_step": 0.5,
            "steps": 300, "step_wall_ms": {}}
EXPECTED = {
    "forward_ms_per_step": 19.0,
    "backward_ms_per_step": 38.0,
    "optimizer_ms_per_step": 2.0,
    "step_unattributed_pct": 5.0,
    "control_host_ms_per_step": 0.875,
    "exchange_calls_per_step": 8.0,
    "exchange_mb_per_step": 860.0,
    "exchange_tail_ms_per_step": 2.3,
}
#: the four counters the engine had before: what the PR's parent hands over
OLD_COUNTERS = {k: v for k, v in COUNTERS.items()
                if k.split("_ms_per_step")[0] in ("pre", "lock_wait", "dispatch", "post", "steps")}


def context(trace=True, counters=COUNTERS):
    return {"trace": {"steps": 6} if trace else None,
            "counters": {"compiles_in_window": 0, "host_overhead": dict(counters)}}


@pytest.fixture()
def summarized(monkeypatch):
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", SUMMARY)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_its_number(name, summarized):
    assert manifest.layer_metric_reader(name)(context()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_without_a_trace(name, summarized):
    assert manifest.layer_metric_reader(name)(context(trace=False)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_on_a_program_without_the_reducer(name, monkeypatch):
    monkeypatch.delattr(trace_analysis, "last_summary")
    assert manifest.layer_metric_reader(name)(context(counters=OLD_COUNTERS)) is None


def test_readers_leave_out_what_the_summary_lacks(monkeypatch):
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", None)
    for name in sorted(set(EXPECTED) - {"control_host_ms_per_step"}):
        assert manifest.layer_metric_reader(name)(context()) is None, name
    # one chip: no collective, so nothing to say of the exchange; no join table,
    # so no share of it
    one_chip = dict(SUMMARY, labeled=False, partition_ms={"unattributed": 80.0},
                    exchange={"calls": 0.0, "bytes": None, "tail_ms": None, "ops": []})
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", one_chip)
    for name in ("exchange_calls_per_step", "exchange_mb_per_step", "exchange_tail_ms_per_step",
                 "step_unattributed_pct", "forward_ms_per_step"):
        assert manifest.layer_metric_reader(name)(context()) is None, name


def test_every_new_metric_has_its_entry_and_the_cells_the_issue_gives():
    entries = {m["name"]: m for m in manifest.benchmark_json()["per_layer"]}
    for name in EXPECTED:
        cells = entries[name].get("workloads")
        assert cells == (["bert-large.dp4"] if name.startswith("exchange_") else None), name
        assert entries[name]["moves"] == "samples_per_s_per_chip"
