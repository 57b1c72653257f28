"""Trace-analyzer interval math and corrupt-capture degradation.

The overlap metric is only as trustworthy as ``_merge_intervals`` /
``_covered`` on the degenerate spans real traces contain — zero-length
events, identical timestamps, fully-nested intervals — and as the loader's
behavior on a capture the profiler never finished writing (job killed
mid-profile): salvage the planes written whole, never raise.
"""

import os
import sys

import pytest

from bagua_tpu.observability.trace_analysis import (
    _covered,
    _merge_intervals,
    analyze_events,
    analyze_trace,
    load_trace_events,
)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ci"))
from trim_capture import xspace_bytes  # noqa: E402  (the PR's own writer of fixtures)


# -- interval math ------------------------------------------------------------


def test_merge_intervals_basic_and_empty():
    assert _merge_intervals([]) == []
    assert _merge_intervals([(1.0, 2.0)]) == [(1.0, 2.0)]
    assert _merge_intervals([(3.0, 4.0), (1.0, 2.0)]) == [(1.0, 2.0), (3.0, 4.0)]
    # touching intervals merge (closed-interval semantics)
    assert _merge_intervals([(1.0, 2.0), (2.0, 3.0)]) == [(1.0, 3.0)]


def test_merge_intervals_zero_length_spans():
    # a zero-length span inside another vanishes into it
    assert _merge_intervals([(0.0, 10.0), (5.0, 5.0)]) == [(0.0, 10.0)]
    # standing alone it survives as a degenerate interval
    assert _merge_intervals([(5.0, 5.0)]) == [(5.0, 5.0)]
    # and glues touching neighbours together
    assert _merge_intervals([(0.0, 5.0), (5.0, 5.0), (5.0, 8.0)]) == [(0.0, 8.0)]


def test_merge_intervals_identical_timestamps():
    assert _merge_intervals([(1.0, 3.0), (1.0, 3.0), (1.0, 3.0)]) == [(1.0, 3.0)]
    # same start, different ends: longest wins
    assert _merge_intervals([(1.0, 2.0), (1.0, 5.0)]) == [(1.0, 5.0)]


def test_merge_intervals_fully_nested():
    assert _merge_intervals([(0.0, 100.0), (10.0, 20.0), (30.0, 40.0)]) == [
        (0.0, 100.0)
    ]
    # nested chain presented inner-first
    assert _merge_intervals([(4.0, 6.0), (2.0, 8.0), (0.0, 10.0)]) == [(0.0, 10.0)]


def covered(start, end, intervals):
    merged = _merge_intervals(list(intervals))
    return _covered(start, end, merged, [s for s, _ in merged])


def test_covered_basic_clipping():
    ivs = [(0.0, 10.0), (20.0, 30.0)]
    assert covered(2.0, 8.0, ivs) == pytest.approx(6.0)       # inside
    assert covered(5.0, 25.0, ivs) == pytest.approx(10.0)     # straddles the gap
    assert covered(-5.0, 50.0, ivs) == pytest.approx(20.0)    # superset
    assert covered(10.0, 20.0, ivs) == pytest.approx(0.0)     # exactly the gap
    assert covered(40.0, 50.0, ivs) == pytest.approx(0.0)     # after everything
    assert covered(-9.0, -1.0, ivs) == pytest.approx(0.0)     # before everything


def test_covered_zero_length_query_and_spans():
    ivs = [(0.0, 10.0)]
    assert covered(5.0, 5.0, ivs) == 0.0        # zero-length query
    assert covered(8.0, 2.0, ivs) == 0.0        # inverted query
    assert covered(5.0, 6.0, []) == 0.0         # no compute at all
    # zero-length compute spans contribute zero coverage
    assert covered(0.0, 10.0, [(5.0, 5.0)]) == 0.0


def test_covered_identical_timestamps_not_double_counted():
    # duplicated compute spans (two lanes, same op) must not double-count
    assert covered(0.0, 4.0, [(1.0, 3.0), (1.0, 3.0)]) == pytest.approx(2.0)


# -- corrupt/truncated captures -----------------------------------------------


def trace_event(hlo_op, ts, dur, device=0, line="t1", module="m"):
    """An operation as ``load_trace_events`` gives it (microseconds)."""
    return {"hlo_op": hlo_op, "hlo_module": module, "lane": (device, line),
            "ts": ts, "dur": dur}


def cpu_plane(events, line="tf_XLAPjRtCpuClient/1"):
    """The ``/host:CPU`` plane of a CPU capture: one executor line whose
    events carry the operation as statistics."""
    return ("/host:CPU", [(line, [
        (e["hlo_op"], 1e3 * e["ts"], 1e3 * e["dur"],
         {"hlo_op": e["hlo_op"], "hlo_module": e["hlo_module"], "run_id": 1,
          "device_ordinal": e["lane"][0]})
        for e in events])])


def write_capture(path, planes):
    with open(path, "wb") as f:
        f.write(xspace_bytes(planes))


def test_analyze_synthetic_trace_overlap_math():
    # compute on lane 1 covers [0,100]; the collective [50,150] on lane 2
    # is half hidden
    rep = analyze_events([
        trace_event("fusion.1", ts=0.0, dur=100.0, line="t1"),
        trace_event("all-reduce.7", ts=50.0, dur=100.0, line="t2"),
    ])
    assert rep["collective_spans"] == 1
    assert rep["measured_overlap_frac"] == pytest.approx(0.5)
    assert rep["per_bucket"] == []  # no HLO text: spans are unattributed
    assert rep["unattributed"]["spans"] == 1


def test_truncated_capture_degrades_to_salvaged_prefix(tmp_path, caplog):
    import logging

    path = str(tmp_path / "t.xplane.pb")
    first = [trace_event(f"fusion.{i}", ts=10.0 * i, dur=5.0) for i in range(300)]
    second = [trace_event(f"fusion.{300 + i}", ts=10.0 * i, dur=5.0, device=1)
              for i in range(200)]
    second.append(trace_event("all-reduce.0", ts=0.0, dur=50.0, device=1))
    write_capture(path, [cpu_plane(first), cpu_plane(second, line="tf_XLAPjRtCpuClient/2")])
    full = load_trace_events(path)
    assert len(full) == 501

    # chop the file inside its second plane: the common killed-mid-profile capture
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) * 4 // 5])
    with caplog.at_level(logging.WARNING,
                         logger="bagua_tpu.observability.trace_analysis"):
        salvaged = load_trace_events(path)
    assert 0 < len(salvaged) < len(full)
    assert any("truncated/corrupt" in r.message for r in caplog.records)
    # the analyzer runs on the salvaged prefix instead of raising
    rep = analyze_trace(path)
    assert rep["num_xla_events"] == len(salvaged)


def test_garbage_payload_degrades_empty(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(b"\x1f\x8b\x08\x00garbage-not-a-capture")
    assert load_trace_events(path) == []


def test_missing_capture_still_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_trace_events(str(tmp_path / "empty_dir"))
