"""``benchmark/run.py`` as the driver calls it, rehearsed on the CPU: the
last line's keys for every cell in ``BENCHMARK.json``, and the refusal to
run without a chip."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

BENCH = manifest.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(*args, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, *BENCH["command"][1].split("/")), *args],
        capture_output=True, text=True, timeout=timeout, cwd=manifest.ROOT,
        env=dict(os.environ, BENCH_RUN="7"),
    )


def later_pr(bench):
    """``bench`` as it will stand once a later PR has appended a metric, a
    cell and a configuration of its own: where the driver puts every new entry."""
    later = copy.deepcopy(bench)
    later["per_layer"].append({
        "name": "a_later_prs_ms_per_step", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "model step", "moves": "samples_per_s_per_chip",
        "workloads": ["a-later-model.dp1"]})
    later["workloads"].append({"name": "a-later-model.dp1", "config": "a-later-model",
                               "traffic": "dp1-b1-s8192", "chips": 1, "why": "a later PR's"})
    later["configs"].append({"name": "a-later-model", "source": "a later PR's",
                             "file": "benchmark/configs/a-later-model.json", "reduced": [],
                             "why": "a later PR's"})
    return later


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_dry_run_prints_the_contracts_last_line(name, trace):
    proc = run_cell("--workload", name, "--seed", str(2**31 + 12345), "--seconds", "2",
                    "--trace", str(trace), "--dry-run")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert all(line.startswith("DRY RUN ") for line in lines[:-1])
    result = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["dry_run"] is True
    cell = manifest.load_cell(name)
    entries = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in entries}
    if trace:
        # what stands on the device's trace or its peaks has nothing to read on the CPU
        assert {"compiles_in_window", "host_ms_per_step"} <= set(result["metrics"]) <= set(units)
        assert result["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert set(result["metrics"]) == set(units)
    for metric, reading in result["metrics"].items():
        assert set(reading) == {"value", "unit"} and reading["unit"] == units[metric]
        assert isinstance(reading["value"], float)
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == cell.chips
    assert "memory_peak_bytes" in result["device"] and "kind" in result["device"]
    assert result["attempted"] > 10 and result["failed"] == 0
    # every number compared is printed beside its limit, in every run, and those lines are
    # the last on standard error
    last = proc.stderr.strip().splitlines()[-len(result["checks"]) - 1:]
    assert last[-1] == "check window_losses_finite=True"
    for number, line in zip(result["checks"], last):
        assert line.startswith(f"check {number}=") and "limit=" in line and line.endswith(" ok")
        assert "DRY RUN " + line in lines
    assert result["correct"] is True, "\n".join(lines[-12:])


def test_without_a_chip_it_fails_and_prints_no_result():
    proc = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
                    timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "tpu" in proc.stderr.lower()


def test_an_unknown_cell_fails_and_prints_no_result():
    proc = run_cell("--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--dry-run", timeout=300)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
