"""The six readers of ``bert-large.dp1-guarded`` over made-up snapshots: each
gives its number where the engine's snapshot has a ``completions`` entry (a
hub was attached), and None on the snapshot of a program without one, which
is every other cell's and the parent's."""

import pytest

from bagua_tpu.observability import trace_analysis
from benchmark import manifest

SUMMARY = {
    "labeled": True,
    "step_busy_ms": 59.0,
    "partition_ms": {"forward": 19.0, "backward": 38.0, "optimizer": 0.5, "health": 1.25,
                     "unattributed": 0.25},
    "exchange": {},
}
BARE = {"pre_ms_per_step": 0.1, "lock_wait_ms_per_step": 0.0, "dispatch_ms_per_step": 2.5,
        "post_ms_per_step": 0.1, "build_ms_per_step": 0.0, "telemetry_ms_per_step": 0.0,
        "health_ms_per_step": 0.0, "next_batch_ms_per_step": 54.0, "loop_ms_per_step": 0.4,
        "steps": 340, "step_wall_ms": {}}
GUARDED = {**BARE, "telemetry_ms_per_step": 0.125, "health_ms_per_step": 0.0625,
           "flight_ms_per_step": 0.25, "health_wait_ms_per_step": 0.0,
           "completions": {"steps": 340, "interval_ms": {"p50": 58.5, "p95": 58.75, "max": 61.0},
                           "run_ahead_mean": 3.0, "stalls": 1, "stall_ms": 2.5,
                           "health_lag_steps_max": 4}}
EXPECTED = {
    "run_ahead_steps": 3.0,
    "health_wait_ms_per_step": 0.0,
    "telemetry_host_ms_per_step": 0.4375,
    "health_device_ms_per_step": 1.25,
    "program_step_ms_p95": 58.75,
    "stall_ms_in_window": 2.5,
}


def context(counters):
    return {"trace": {"steps": 6},
            "counters": {"compiles_in_window": 0, "host_overhead": dict(counters)}}


@pytest.fixture()
def summarized(monkeypatch):
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", SUMMARY)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_its_number_with_a_hub(name, summarized):
    assert manifest.layer_metric_reader(name)(context(GUARDED)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_without_a_hub(name, summarized):
    assert manifest.layer_metric_reader(name)(context(BARE)) is None


def test_a_step_whose_health_scalars_fused_away_reads_zero(monkeypatch):
    fused = {**SUMMARY, "partition_ms": {k: v for k, v in SUMMARY["partition_ms"].items()
                                         if k != "health"}}
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", fused)
    assert manifest.layer_metric_reader("health_device_ms_per_step")(context(GUARDED)) == 0.0


def test_a_hub_without_a_recorder_has_no_flight_counter(summarized):
    counters = {k: v for k, v in GUARDED.items() if k != "flight_ms_per_step"}
    read = manifest.layer_metric_reader("telemetry_host_ms_per_step")
    assert read(context(counters)) == pytest.approx(0.1875)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_metric_lists_the_guarded_cell_alone(name):
    entry, = [m for m in manifest.benchmark_json()["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["bert-large.dp1-guarded"]
