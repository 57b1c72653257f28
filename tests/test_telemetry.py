"""Telemetry hub: in-graph labels, trace analyzer, recompile detector, metrics.

Pins the observability contract end-to-end on the 8-device CPU sim:

* every bucket exchange in the compiled step carries a parseable
  ``bagua_ex/algo=<a>/bucket=<i>/phase=<p>`` scope (and the engine phases a
  ``bagua_step/phase=<p>`` scope) — for both the overlap and monolithic paths;
* the device-trace analyzer attributes the captured collective spans back to
  the bucket plan: one ``per_bucket`` row per plan bucket, labels matching;
* the recompile detector reports zero retraces across steady-state steps and
  at least one (plus a rate alert) when the jit cache churns;
* the metrics layer (registry, JSONL sink, Prometheus text export) and the
  StepTimer/Watchdog satellites behave as documented.
"""

import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import (
    Counter,
    Histogram,
    JsonlSink,
    MetricsRegistry,
    ProfilerSession,
    RecompileDetector,
    StepTimer,
    Telemetry,
    Watchdog,
    analyze_trace,
    parse_exchange_label,
    parse_step_phase,
    rotated_metrics_files,
    validate_metrics_event,
    validate_metrics_file,
)

GLOBAL_BATCH = 32
LAYERS = [12, 16, 16, 4]


def make_batch(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(GLOBAL_BATCH, LAYERS[0]).astype(np.float32))
    y = jnp.asarray(rng.randn(GLOBAL_BATCH, LAYERS[-1]).astype(np.float32))
    return x, y


def make_ddp(group, overlap, telemetry=None, bucket_size=1 << 9):
    return DistributedDataParallel(
        mse_loss,
        optax.sgd(0.1),
        GradientAllReduceAlgorithm(),
        process_group=group,
        bucket_size_bytes=bucket_size,  # small: forces several buckets
        overlap=overlap,
        telemetry=telemetry,
    )


def compiled_hlo(ddp, state, batch):
    """Compiled HLO text of the step variant last dispatched."""
    return ddp.compiled_step().as_text()


def op_name_labels(hlo):
    return re.findall(r'op_name="([^"]*)"', hlo)


# -- scope grammar round-trips ------------------------------------------------


def test_parse_exchange_label_roundtrip():
    lab = parse_exchange_label(
        "jit(step)/bagua_ex/algo=bytegrad/bucket=12/phase=mono/convert"
    )
    assert lab == {"algo": "bytegrad", "bucket": 12, "phase": "mono"}
    assert parse_exchange_label("jit(step)/transpose/all-reduce") is None
    assert parse_exchange_label("") is None and parse_exchange_label(None) is None


def test_parse_step_phase():
    assert parse_step_phase("jit(step)/bagua_step/phase=fwd_bwd/dot") == "fwd_bwd"
    assert parse_step_phase("jit(step)/dot") is None


# -- in-graph annotations in the compiled step --------------------------------


def test_overlap_step_hlo_carries_bucket_labels(group):
    """Every plan bucket's exchange is labeled phase=overlap in the compiled
    overlap step, and the engine phases are labeled too."""
    ddp = make_ddp(group, overlap=True)
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    state, _ = ddp.train_step(state, batch)
    assert ddp.plan.num_buckets > 1  # multi-bucket: labels are per-bucket facts

    labels = op_name_labels(compiled_hlo(ddp, state, batch))
    ex = [lab for lab in map(parse_exchange_label, labels) if lab]
    assert ex, "no bucket-exchange labels in compiled HLO"
    assert {e["algo"] for e in ex} == {"gradient_allreduce"}
    assert {e["phase"] for e in ex} == {"overlap"}
    assert {e["bucket"] for e in ex} == set(range(ddp.plan.num_buckets))

    phases = {p for p in map(parse_step_phase, labels) if p}
    assert "fwd_bwd" in phases and "optimizer" in phases


def test_monolithic_step_hlo_carries_mono_labels(group):
    ddp = make_ddp(group, overlap=False)
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    state, _ = ddp.train_step(state, batch)

    labels = op_name_labels(compiled_hlo(ddp, state, batch))
    ex = [lab for lab in map(parse_exchange_label, labels) if lab]
    assert {e["phase"] for e in ex} == {"mono"}
    assert {e["bucket"] for e in ex} == set(range(ddp.plan.num_buckets))


# -- trace analyzer on a CPU-captured profiler session ------------------------


def test_trace_analyzer_attributes_plan_buckets(group, tmp_path):
    """Acceptance: the analyzer's per-bucket collective spans match the
    bucket plan (count and labels) on a CPU ProfilerSession capture."""
    ddp = make_ddp(group, overlap=True)
    state = ddp.init(init_mlp(jax.random.PRNGKey(1), LAYERS))
    batch = make_batch(seed=1)
    state, _ = ddp.train_step(state, batch)  # warmup compile outside capture
    hlo = compiled_hlo(ddp, state, batch)

    prof_dir = str(tmp_path / "trace")
    prof = ProfilerSession(prof_dir)
    state, _ = prof.trace_steps(ddp.train_step, state, [batch, batch])

    report = analyze_trace(prof_dir, hlo_text=hlo)
    assert report["collective_spans"] > 0
    assert 0.0 <= report["measured_overlap_frac"] <= 1.0

    # the compiler may combine several buckets' all-reduces into one, which
    # then carries one constituent's label: a row per bucket that still has
    # an operation of its own, every one a plan bucket
    rows = report["per_bucket"]
    assert 1 <= len(rows) <= ddp.plan.num_buckets
    assert {r["bucket"] for r in rows} <= set(range(ddp.plan.num_buckets))
    for r in rows:
        assert r["algo"] == "gradient_allreduce"
        assert r["phases"] == ["overlap"]
        assert r["spans"] > 0
        assert all(op.startswith(("all-reduce", "psum")) for op in r["hlo_ops"])
    # the step's only collectives are the labeled bucket exchanges
    assert report["unattributed"] is None
    ddp.shutdown()


def test_trace_analyzer_without_hlo_is_aggregate_only(group, tmp_path):
    ddp = make_ddp(group, overlap=True)
    state = ddp.init(init_mlp(jax.random.PRNGKey(2), LAYERS))
    batch = make_batch(seed=2)
    state, _ = ddp.train_step(state, batch)

    prof_dir = str(tmp_path / "trace")
    state, _ = ProfilerSession(prof_dir).trace_steps(ddp.train_step, state, [batch])

    report = analyze_trace(prof_dir)  # no hlo_text: no join table
    assert report["collective_spans"] > 0
    assert report["per_bucket"] == []
    assert report["unattributed"]["spans"] == report["collective_spans"]
    ddp.shutdown()


# -- recompile detector -------------------------------------------------------


def test_recompile_detector_steady_state_is_quiet():
    det = RecompileDetector()
    assert det.record_compile("default") is False  # warmup, not a retrace
    for _ in range(5):
        det.record_step()
    rep = det.report()
    assert rep == {
        "steps": 5, "retraces": 0, "alerts": 0,
        "compiles_by_variant": {"default": 1},
        "compile_ms_total": 0.0, "compile_ms_by_variant": {},
    }


def test_recompile_detector_counts_retraces_and_alerts():
    alerts = []
    on_alert = lambda msg, n: alerts.append((msg, n))  # noqa: E731
    det = RecompileDetector(window=10, max_retraces_per_window=1)
    det.record_compile("a", on_alert=on_alert)  # warmup
    assert det.record_compile("b", on_alert=on_alert) is True  # new variant = retrace
    assert det.record_compile("a", on_alert=on_alert) is True  # re-build = retrace
    det.record_compile("a", on_alert=on_alert)
    rep = det.report()
    assert rep["retraces"] == 3
    assert rep["alerts"] == 1 and len(alerts) == 1  # latched: one alarm
    assert "retraces in the last 10 steps" in alerts[0][0]


def test_recompile_detector_rearms_after_quiet_window():
    det = RecompileDetector(window=3, max_retraces_per_window=0)
    det.record_compile("v")
    det.record_compile("v")  # retrace -> alert #1
    assert det.report()["alerts"] == 1
    for _ in range(3):  # a full quiet window re-arms the alarm
        det.record_step()
    det.record_compile("v")  # retrace -> alert #2
    assert det.report() == {
        "steps": 3, "retraces": 2, "alerts": 2,
        "compiles_by_variant": {"v": 3},
        "compile_ms_total": 0.0, "compile_ms_by_variant": {},
    }


def test_ddp_telemetry_steady_state_then_forced_retrace(group, tmp_path):
    """Acceptance: 0 retraces across 5 steady-state MLP steps; clearing the
    jit cache (what need_reset/rebucket do) makes the next step a retrace."""
    jsonl = str(tmp_path / "metrics.jsonl")
    tel = Telemetry(metrics_jsonl=jsonl, max_retraces_per_window=0)
    ddp = make_ddp(group, overlap=True, telemetry=tel)
    state = ddp.init(init_mlp(jax.random.PRNGKey(3), LAYERS))
    batch = make_batch(seed=3)
    for _ in range(5):
        state, _ = ddp.train_step(state, batch)
    rep = tel.recompile.report()
    assert rep["steps"] == 5 and rep["retraces"] == 0 and rep["alerts"] == 0

    ddp.drop_step_variants()  # forced cache churn: the step variant must rebuild
    state, _ = ddp.train_step(state, batch)
    rep = tel.recompile.report()
    assert rep["retraces"] == 1 and rep["alerts"] == 1

    ddp.drain_steps()  # the hub counts a step's wall when it is seen to complete
    snap = tel.snapshot()
    assert snap["phase"] == "wait" and snap["step"] == 5
    assert snap["completed_step"] == 5 and snap["run_ahead"] == 0
    assert snap["metrics"]["steps_total"] == 6
    assert snap["metrics"]["retrace_alerts_total"] == 1
    assert snap["metrics"]["step_wall_ms"]["count"] == 6
    # engine satellite: step-wall percentiles surfaced host-side
    assert set(ddp.host_overhead_snapshot()["step_wall_ms"]) == {"p50", "p95", "p99"}

    tel.close()
    assert validate_metrics_file(jsonl) == []
    with open(jsonl) as f:
        events = [json.loads(line) for line in f if line.strip()]
    kinds = [e["event"] for e in events]
    assert kinds.count("step") == 6
    assert kinds.count("compile") == 2  # warmup + forced retrace
    assert kinds.count("retrace_alert") == 1
    retraced = [e["retrace"] for e in events if e["event"] == "compile"]
    assert retraced == [False, True]
    step_ev = next(e for e in events if e["event"] == "step")
    assert step_ev["wire_bytes"] == ddp.plan.total_bytes()
    assert "host_overhead_ms" in step_ev

    prom_path = str(tmp_path / "metrics.prom")
    tel.export_prometheus(prom_path)
    prom = open(prom_path).read()
    assert "bagua_steps_total 6" in prom
    assert "bagua_retraces_total 1" in prom
    assert "bagua_step_wall_ms_count 6" in prom
    ddp.shutdown()


# -- metrics layer ------------------------------------------------------------


def test_metrics_registry_instruments():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)  # counters are monotonic
    with pytest.raises(TypeError):
        reg.gauge("c")  # kind mismatch under one name
    reg.gauge("g").set(1.5)
    for v in range(1, 101):
        reg.histogram("h").observe(float(v))
    snap = reg.snapshot()
    assert snap["c"] == 3 and snap["g"] == 1.5
    # nearest-rank: the p50 of 1..100 is the 50th smallest sample
    assert snap["h"]["count"] == 100 and snap["h"]["p50"] == 50.0

    prom = reg.to_prometheus()
    assert "# TYPE bagua_c counter" in prom and "bagua_c 3" in prom
    assert "# TYPE bagua_g gauge" in prom
    # histograms export as conformant summaries: quantile-labeled samples
    # (bare quantile values, "0.5" not "0.50") followed by _count/_sum
    assert 'bagua_h{quantile="0.5"} 50.0' in prom
    assert 'bagua_h{quantile="0.95"}' in prom and 'bagua_h{quantile="0.99"}' in prom
    assert "bagua_h_count 100" in prom
    assert f"bagua_h_sum {float(sum(range(1, 101)))}" in prom
    # quantile samples precede the _count/_sum pair within the family
    assert prom.index('bagua_h{quantile="0.5"}') < prom.index("bagua_h_count")


def test_histogram_window_is_recent_tail():
    h = Histogram("h", window=100)
    for v in range(1, 2001):
        h.observe(float(v))
    # percentiles over the last 100 observations (1901..2000), not the run
    assert h.percentiles()["p50"] == 1950.0
    assert h.count == 2000 and h.sum == sum(range(1, 2001))


def test_event_schema_validation(tmp_path):
    ok = {"ts": 1.0, "event": "step", "step": 3, "wall_ms": 1.0,
          "samples_per_s": 2.0, "wire_bytes": 8, "variant": "default"}
    assert validate_metrics_event(ok) == []
    assert validate_metrics_event({"event": "step"})  # missing envelope+payload
    assert validate_metrics_event({"ts": "now", "event": "x", "step": 0})

    path = str(tmp_path / "ev.jsonl")
    with JsonlSink(path) as sink:
        sink.emit(dict(ok))
        sink.emit({"event": "custom", "step": 0})  # unknown type: envelope only
        with pytest.raises(ValueError):
            sink.emit({"event": "compile", "step": 1})  # missing payload fields
    assert validate_metrics_file(path) == []
    with open(path, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"event": "step", "step": "three", "ts": 0}) + "\n")
    problems = validate_metrics_file(path)
    assert any("not JSON" in p for p in problems)
    assert any("'step'" in p for p in problems)


def test_jsonl_sink_rotation_and_rotated_validation(tmp_path, monkeypatch):
    path = str(tmp_path / "m.jsonl")
    ev = {"event": "custom", "step": 0, "ts": 1.0}
    line_len = len(json.dumps(ev, sort_keys=True)) + 1
    # room for ~2 lines per file: every 3rd emit rotates
    with JsonlSink(path, max_bytes=2 * line_len + 1) as sink:
        for i in range(7):
            sink.emit({"event": "custom", "step": i, "ts": 1.0})
    files = rotated_metrics_files(path)
    assert files[-1] == path and len(files) > 1
    assert all(os.path.exists(f) for f in files)
    # no event lost, order preserved oldest-file-first, no line split
    steps = []
    for f in files:
        with open(f) as fh:
            steps.extend(json.loads(ln)["step"] for ln in fh)
    assert steps == list(range(7))
    assert validate_metrics_file(path) == []
    # a bad line in a rotated segment is reported with the segment's name
    with open(files[0], "a") as fh:
        fh.write("not json\n")
    problems = validate_metrics_file(path)
    assert any(os.path.basename(files[0]) in p for p in problems)

    # default off: no rotation regardless of size
    monkeypatch.delenv("BAGUA_METRICS_MAX_MB", raising=False)
    path2 = str(tmp_path / "n.jsonl")
    with JsonlSink(path2) as sink:
        for i in range(50):
            sink.emit({"event": "custom", "step": i, "ts": 1.0})
    assert rotated_metrics_files(path2) == [path2]

    # BAGUA_METRICS_MAX_MB drives the default ceiling (fractional MiB ok)
    monkeypatch.setenv("BAGUA_METRICS_MAX_MB", str(2 * line_len / (1 << 20)))
    path3 = str(tmp_path / "o.jsonl")
    with JsonlSink(path3) as sink:
        for i in range(5):
            sink.emit({"event": "custom", "step": i, "ts": 1.0})
    assert len(rotated_metrics_files(path3)) > 1


# -- StepTimer and Watchdog satellites ----------------------------------------


def test_step_timer_percentiles_and_thread_safety():
    timer = StepTimer(window=64)
    assert timer.percentiles() == {}

    def worker():
        for _ in range(100):
            timer.tick(0.01)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert timer.n_steps == 400
    p = timer.percentiles()
    assert p["p50"] == p["p95"] == p["p99"] == 0.01


def test_step_timer_small_ring_quantiles_nearest_rank():
    """Nearest-rank indexing on tiny rings: the old ``int(p * n)`` bias made
    the p50 of a 2-sample ring return the MAX.  Pin the corrected values for
    1-, 2- and 3-sample rings (and the Histogram twin, same indexing)."""
    timer = StepTimer(window=8)
    timer.tick(0.5)
    assert timer.percentiles() == {"p50": 0.5, "p95": 0.5, "p99": 0.5}

    timer = StepTimer(window=8)
    timer.tick(0.010)
    timer.tick(0.020)
    p = timer.percentiles()
    assert p["p50"] == 0.010  # the LOWER sample, not the max
    assert p["p95"] == 0.020 and p["p99"] == 0.020

    timer = StepTimer(window=8)
    for v in (0.030, 0.010, 0.020):
        timer.tick(v)
    p = timer.percentiles()
    assert p["p50"] == 0.020 and p["p95"] == 0.030 and p["p99"] == 0.030

    h = Histogram("h", window=8)
    h.observe(1.0)
    h.observe(2.0)
    assert h.percentiles()["p50"] == 1.0


def test_watchdog_env_override(monkeypatch):
    monkeypatch.setenv("BAGUA_WATCHDOG_TIMEOUT_S", "7.5")
    assert Watchdog(timeout_s=300.0).timeout_s == 7.5
    monkeypatch.setenv("BAGUA_WATCHDOG_TIMEOUT_S", "not-a-number")
    assert Watchdog(timeout_s=300.0).timeout_s == 300.0  # ignored, not fatal
    monkeypatch.delenv("BAGUA_WATCHDOG_TIMEOUT_S")
    assert Watchdog(timeout_s=120.0).timeout_s == 120.0


def test_watchdog_timeout_context_carries_telemetry():
    tel = Telemetry()
    tel.current_step, tel.current_phase = 7, "dispatch"
    wd = Watchdog(timeout_s=60.0, snapshot_provider=tel.snapshot)
    wd.beat(phase="dispatch")
    ctx = wd._timeout_context()
    assert ctx["last_phase"] == "dispatch"
    assert ctx["telemetry"]["step"] == 7 and ctx["telemetry"]["phase"] == "dispatch"

    def bad():
        raise RuntimeError("boom")

    wd.snapshot_provider = bad
    ctx = wd._timeout_context()  # a broken hook must not lose the dump
    assert "telemetry" not in ctx and "boom" in ctx["telemetry_error"]


def test_watchdog_fires_with_phase_tag(tmp_path):
    fired = []
    wd = Watchdog(
        timeout_s=0.15, check_interval_s=0.05, on_timeout=lambda s: fired.append(s)
    )
    wd.dump_dir = str(tmp_path)  # the timeout path now leaves evidence files
    wd.start()
    wd.beat(phase="wait")
    deadline = time.time() + 3.0
    while not fired and time.time() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert fired and wd.last_phase == "wait"


def test_telemetry_wires_watchdog_snapshot():
    wd = Watchdog(timeout_s=60.0)
    tel = Telemetry(watchdog=wd)
    assert wd.snapshot_provider == tel.snapshot  # bound to this hub
    tel.enter_phase("data")
    assert wd.last_phase == "data" and tel.current_phase == "data"


def test_on_rebucket_counter_gauges_and_event(tmp_path):
    """A plan swap shows up on every telemetry surface at once: the
    ``rebucket_total`` counter, the ``plan_version`` gauge, the optional
    predicted/measured exposed-comm gauges, a schema-valid ``rebucket`` JSONL
    event, and the Prometheus text export."""
    path = str(tmp_path / "m.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    tel.on_rebucket(plan_version=1, n_buckets=4, step=7, predicted_exposed_ms=12.5)
    tel.on_rebucket(plan_version=2, n_buckets=2, step=9, measured_exposed_ms=3.25)
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["rebucket_total"] == 2
    assert snap["plan_version"] == 2.0
    assert snap["predicted_exposed_comm_ms"] == 12.5
    assert snap["measured_exposed_comm_ms"] == 3.25

    from bagua_tpu.observability import validate_metrics_file

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    rb = [e for e in events if e["event"] == "rebucket"]
    assert [e["plan_version"] for e in rb] == [1, 2]
    assert rb[0]["n_buckets"] == 4 and rb[0]["step"] == 7
    assert rb[0]["predicted_exposed_ms"] == 12.5
    assert "predicted_exposed_ms" not in rb[1]  # optional field stays absent
    assert rb[1]["measured_exposed_ms"] == 3.25

    prom = tel.registry.to_prometheus()
    assert "bagua_rebucket_total 2" in prom
    assert "bagua_plan_version 2" in prom


def test_precision_switch_event_schema():
    """``precision_switch`` is a first-class schema-validated event type:
    the before/after per-bucket precision lists and the reason are required,
    typed payload fields."""
    ok = {"ts": 1.0, "event": "precision_switch", "step": 4, "plan_version": 0,
          "old_precisions": ["f32", "f32"], "new_precisions": ["int8", "f32"],
          "reason": "planner"}
    assert validate_metrics_event(ok) == []
    missing = dict(ok)
    del missing["new_precisions"]
    assert any("'new_precisions'" in p for p in validate_metrics_event(missing))
    badtype = dict(ok, old_precisions="f32")
    assert any("'old_precisions'" in p for p in validate_metrics_event(badtype))


def test_on_precision_switch_surfaces(tmp_path):
    """A wire-precision plan swap lands on every telemetry surface at once:
    the ``precision_switch_total`` counter, per-precision bucket-count
    gauges, a schema-valid JSONL event, and the Prometheus export."""
    path = str(tmp_path / "p.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    tel.on_precision_switch(
        step=3, plan_version=0, old_precisions=["f32", "f32", "f32"],
        new_precisions=["int8", "f32", "int4"],
    )
    tel.on_precision_switch(
        step=9, plan_version=0, old_precisions=["int8", "f32", "int4"],
        new_precisions=["int8", "int8", "int4"], reason="manual",
    )
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["precision_switch_total"] == 2
    assert snap["buckets_at_precision_int8"] == 2.0
    assert snap["buckets_at_precision_int4"] == 1.0

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    sw = [e for e in events if e["event"] == "precision_switch"]
    assert [e["reason"] for e in sw] == ["planner", "manual"]
    assert sw[0]["old_precisions"] == ["f32", "f32", "f32"]
    assert sw[0]["new_precisions"] == ["int8", "f32", "int4"]
    assert sw[1]["step"] == 9

    prom = tel.registry.to_prometheus()
    assert "bagua_precision_switch_total 2" in prom
    assert "bagua_buckets_at_precision_int8 2" in prom


def test_on_step_per_precision_wire_counters(tmp_path):
    """``wire_bytes_by_precision`` splits the census into per-precision
    counters (the flat-name labeled family) and rides the step JSONL event."""
    path = str(tmp_path / "w.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    by_prec = {"f32": 1000, "int8": 300, "int4": 150}
    for step in range(3):
        tel.on_step(step=step, wall_s=0.01, n_samples=32, wire_bytes=1450,
                    wire_bytes_by_precision=by_prec)
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["wire_bytes_precision_f32_total"] == 3000
    assert snap["wire_bytes_precision_int8_total"] == 900
    assert snap["wire_bytes_precision_int4_total"] == 450
    assert snap["wire_bytes_total"] == 3 * 1450

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    steps = [e for e in events if e["event"] == "step"]
    assert all(e["wire_bytes_by_precision"] == by_prec for e in steps)


def test_precision_plan_switch_emits_telemetry_from_engine(group, tmp_path):
    """End-to-end: ``apply_precision_plan`` on an ``auto`` engine emits the
    ``precision_switch`` event and subsequent steps feed the per-precision
    wire-byte counters with the modelled quantized-ring bytes."""
    from bagua_tpu.kernels.quantized_ring import ring_wire_bytes

    path = str(tmp_path / "pe.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05),
        GradientAllReduceAlgorithm(wire_precision="auto"),
        process_group=group, bucket_size_bytes=1 << 9, telemetry=tel,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    state, _ = ddp.train_step(state, batch)

    nb = ddp.plan.num_buckets
    assert nb >= 2
    plan = ["int8"] + ["f32"] * (nb - 1)
    assert ddp.apply_precision_plan(plan, reason="manual")
    state, _ = ddp.train_step(state, batch)
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["precision_switch_total"] == 1
    assert snap["buckets_at_precision_int8"] == 1.0
    assert snap["buckets_at_precision_f32"] == float(nb - 1)
    # step 1 ran all-f32, step 2 ran the mixed plan: the int8 counter holds
    # exactly one step's modelled ring bytes for bucket 0
    n = group.size
    assert snap["wire_bytes_precision_int8_total"] == ring_wire_bytes(
        ddp.plan.specs[0].numel, n, 8
    )

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    (sw,) = [e for e in events if e["event"] == "precision_switch"]
    assert sw["old_precisions"] == ["f32"] * nb
    assert sw["new_precisions"] == plan and sw["reason"] == "manual"
    step_events = [e for e in events if e["event"] == "step"]
    assert "wire_bytes_by_precision" in step_events[-1]
    assert step_events[-1]["wire_bytes_by_precision"]["int8"] > 0
    ddp.shutdown()


def test_snapshot_and_restart_event_schemas(tmp_path):
    """The resilience subsystem's JSONL events are schema-validated like
    every other event type: required payload fields, typed, with torn or
    truncated records reported rather than crashing the validator."""
    snap_ok = {"ts": 1.0, "event": "snapshot", "step": 6,
               "wall_ms": 12.5, "bytes": 4096, "kind": "async"}
    restart_ok = {"ts": 2.0, "event": "restart", "step": 6,
                  "old_world_size": 8, "new_world_size": 4,
                  "plan_source": "carried", "lost_steps": 2}
    assert validate_metrics_event(snap_ok) == []
    assert validate_metrics_event(restart_ok) == []

    missing = dict(snap_ok)
    del missing["kind"]
    assert any("'kind'" in p for p in validate_metrics_event(missing))
    badtype = dict(restart_ok, lost_steps="two")
    assert any("'lost_steps'" in p for p in validate_metrics_event(badtype))

    path = str(tmp_path / "r.jsonl")
    with JsonlSink(path) as sink:
        sink.emit(dict(snap_ok))
        sink.emit(dict(restart_ok))
        with pytest.raises(ValueError):  # the sink refuses incomplete events
            sink.emit({"event": "restart", "step": 1})
    assert validate_metrics_file(path) == []


def test_on_snapshot_and_on_restart_surfaces(tmp_path):
    """A snapshot write and an elastic resume land on every telemetry surface
    at once: counters/gauges/histograms, schema-valid JSONL events, and the
    Prometheus text export."""
    path = str(tmp_path / "res.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    tel.on_snapshot(step=3, wall_ms=7.25, n_bytes=1 << 20, kind="async")
    tel.on_snapshot(step=6, wall_ms=9.0, n_bytes=1 << 20, kind="final")
    tel.on_restart(step=6, old_world_size=8, new_world_size=4,
                   plan_source="carried", lost_steps=2)
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["snapshots_total"] == 2
    assert snap["snapshot_last_step"] == 6.0
    assert snap["snapshot_wall_ms"]["count"] == 2
    assert snap["restarts_total"] == 1
    assert snap["lost_steps_total"] == 2
    assert snap["resumed_world_size"] == 4.0

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    snaps = [e for e in events if e["event"] == "snapshot"]
    assert [e["kind"] for e in snaps] == ["async", "final"]
    assert snaps[0]["bytes"] == 1 << 20 and snaps[0]["wall_ms"] == 7.25
    (restart,) = [e for e in events if e["event"] == "restart"]
    assert restart["step"] == 6 and restart["plan_source"] == "carried"
    assert restart["old_world_size"] == 8 and restart["new_world_size"] == 4

    prom = tel.registry.to_prometheus()
    assert "bagua_snapshots_total 2" in prom
    assert "bagua_restarts_total 1" in prom
    assert "bagua_lost_steps_total 2" in prom
    assert "bagua_snapshot_wall_ms_count 2" in prom


def test_rebucket_emits_telemetry_from_engine(group, tmp_path):
    """End-to-end: DistributedDataParallel.rebucket bumps plan_version and
    feeds the hub; training continues on the new plan."""
    from bagua_tpu.bucket import BucketPlan
    from bagua_tpu.models.mlp import init_mlp

    path = str(tmp_path / "e.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    params = init_mlp(jax.random.PRNGKey(0), [16, 32, 4])
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(),
        process_group=group, bucket_size_bytes=1 << 10, telemetry=tel,
    )
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(16, 16), np.float32),
        jnp.asarray(rng.randn(16, 4), np.float32),
    )
    state, _ = ddp.train_step(state, batch)
    assert ddp.plan_version == 0

    coarse = BucketPlan.from_declarations(
        [[td for b in ddp.plan.declarations() for td in b]],  # one mega-bucket
        ddp._tree_template, align_elems=group.size,
    )
    ddp.rebucket(coarse, predicted_exposed_ms=1.5)
    assert ddp.plan_version == 1
    snap = tel.registry.snapshot()
    assert snap["rebucket_total"] == 1 and snap["plan_version"] == 1.0
    assert snap["predicted_exposed_comm_ms"] == 1.5

    state, losses = ddp.train_step(state, batch)
    assert np.isfinite(np.asarray(losses)).all()
    tel.close()
    events = [json.loads(l) for l in open(path) if l.strip()]
    assert any(e["event"] == "rebucket" and e["plan_version"] == 1 for e in events)


# -- model-parallel scope grammar + per-scope trace attribution ---------------

from jax.sharding import PartitionSpec as P  # noqa: E402


def test_parse_mp_label_roundtrip():
    from bagua_tpu.observability import mp_scope, parse_mp_label

    lab = parse_mp_label("jit(f)/bagua_ex/axis=tp/phase=rs_ring/collective-permute")
    assert lab == {"axis": "tp", "phase": "rs_ring"}
    # the two grammars never cross-match: algo=/bucket= vs axis=
    assert parse_mp_label(
        "jit(step)/bagua_ex/algo=bytegrad/bucket=12/phase=mono/convert"
    ) is None
    assert parse_exchange_label("jit(f)/bagua_ex/axis=tp/phase=rs_ring/x") is None
    assert parse_mp_label("") is None and parse_mp_label(None) is None
    # the scope emits what the parser reads
    with mp_scope("ep", "dispatch"):
        pass


def test_fused_tp_hlo_carries_mp_labels():
    """The fused RowParallel ring's collectives carry axis=tp labels in the
    compiled HLO (rs_ring on the ppermutes, row_allgather on the gather)."""
    from jax.sharding import Mesh
    from bagua_tpu.observability import parse_mp_label
    from bagua_tpu.parallel.tensor_parallel import ParallelMLP

    tp = 8
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 12).astype(np.float32))
    mlp = ParallelMLP(hidden_features=16, out_features=8, tp_size=tp, fused="auto")
    per_rank = [mlp.init(jax.random.PRNGKey(r), x)["params"] for r in range(tp)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    hlo = (
        jax.jit(
            jax.shard_map(
                lambda p, xx: mlp.apply(
                    {"params": jax.tree.map(lambda q: q[0], p)}, xx
                ),
                mesh=mesh, in_specs=(P("tp"), P()), out_specs=P(),
                check_vma=False,
            )
        )
        .lower(stacked, x)
        .compile()
        .as_text()
    )
    mp = [lab for lab in map(parse_mp_label, op_name_labels(hlo)) if lab]
    assert mp, "no model-parallel labels in compiled fused HLO"
    assert {m["axis"] for m in mp} == {"tp"}
    assert {"rs_ring", "row_allgather"} <= {m["phase"] for m in mp}


def test_trace_analyzer_per_scope_rows(tmp_path):
    """analyze_trace attributes mp-labeled collectives into per_scope rows
    with their own measured_overlap_frac (the tp/ep scope report)."""
    from jax.sharding import Mesh
    from bagua_tpu.parallel.tensor_parallel import ParallelMLP

    tp = 8
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(16, 16).astype(np.float32))
    mlp = ParallelMLP(hidden_features=32, out_features=16, tp_size=tp, fused="auto")
    per_rank = [mlp.init(jax.random.PRNGKey(r), x)["params"] for r in range(tp)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    step = jax.jit(
        jax.shard_map(
            lambda p, xx: mlp.apply({"params": jax.tree.map(lambda q: q[0], p)}, xx),
            mesh=mesh, in_specs=(P("tp"), P()), out_specs=P(), check_vma=False,
        )
    )
    compiled = step.lower(stacked, x).compile()
    compiled(stacked, x).block_until_ready()  # warm outside the capture

    prof_dir = str(tmp_path / "trace")
    with ProfilerSession(prof_dir):
        for _ in range(3):
            compiled(stacked, x).block_until_ready()

    report = analyze_trace(prof_dir, hlo_text=compiled.as_text())
    rows = {r["axis"]: r for r in report["per_scope"]}
    assert "tp" in rows, report
    row = rows["tp"]
    assert {"rs_ring", "row_allgather"} <= set(row["phases"])
    assert row["spans"] > 0 and row["collective_ms"] > 0
    assert 0.0 <= row["measured_overlap_frac"] <= 1.0
    # told by opcode: JAX names the instruction after its primitive
    assert any(op.startswith(("collective-permute", "ppermute")) for op in row["hlo_ops"])
    # the mp-labeled collectives are not double-counted as bucket exchanges
    assert report["per_bucket"] == []


# -- circuit-breaker transition telemetry -------------------------------------


def test_breaker_transition_event_schema():
    ok = {"ts": 1.0, "event": "breaker_transition", "step": 2,
          "breaker": "fleet-rpc", "old_state": "closed", "new_state": "open"}
    assert validate_metrics_event(ok) == []
    missing = dict(ok)
    del missing["new_state"]
    assert any("'new_state'" in p for p in validate_metrics_event(missing))
    badtype = dict(ok, old_state=1)
    assert any("'old_state'" in p for p in validate_metrics_event(badtype))


def test_breaker_transitions_land_on_telemetry(tmp_path):
    """A full breaker cycle (closed -> open -> half-open -> closed) lands on
    every telemetry surface: the shared + per-breaker state gauges, the
    transition counter, schema-valid JSONL events, and the Prometheus
    export.  ``bind_breaker`` is idempotent and never usurps a listener."""
    from bagua_tpu.resilience.retry import CircuitBreaker, CircuitOpenError

    path = str(tmp_path / "b.jsonl")
    tel = Telemetry(metrics_jsonl=path)
    tel.current_step = 12
    clk = [0.0]
    breaker = CircuitBreaker(failure_threshold=2, cooldown_s=5.0,
                             name="auto-rpc", clock=lambda: clk[0])
    tel.bind_breaker(breaker)
    assert breaker.listener == tel.on_breaker_transition
    tel.bind_breaker(breaker)  # idempotent
    assert breaker.listener == tel.on_breaker_transition
    taken = CircuitBreaker(name="other", listener=lambda *a: None)
    already = taken.listener
    tel.bind_breaker(taken)  # an explicit listener is left alone
    assert taken.listener is already

    breaker.record_failure()  # 1/2: still closed, no transition
    breaker.record_failure()  # 2/2: closed -> open
    assert tel.registry.snapshot()["breaker_state"] == 2.0
    with pytest.raises(CircuitOpenError):
        breaker.before_call()  # still cooling down: no transition
    clk[0] = 6.0
    breaker.before_call()  # cooldown over: open -> half-open (the probe)
    assert tel.registry.snapshot()["breaker_state"] == 1.0
    breaker.record_success()  # probe landed: half-open -> closed
    tel.close()

    snap = tel.registry.snapshot()
    assert snap["breaker_state"] == 0.0
    assert snap["breaker_state_auto_rpc"] == 0.0  # name sanitized for the gauge
    assert snap["breaker_transitions_total"] == 3

    assert validate_metrics_file(path) == []
    events = [json.loads(l) for l in open(path) if l.strip()]
    trans = [e for e in events if e["event"] == "breaker_transition"]
    assert [(e["old_state"], e["new_state"]) for e in trans] == [
        ("closed", "open"), ("open", "half-open"), ("half-open", "closed")]
    assert all(e["breaker"] == "auto-rpc" and e["step"] == 12 for e in trans)

    prom = tel.registry.to_prometheus()
    assert "bagua_breaker_state 0" in prom
    assert "bagua_breaker_transitions_total 3" in prom


# -- budget attribution / regression sentinel ---------------------------------


from bagua_tpu.observability import (  # noqa: E402
    BUDGET_COMPONENTS,
    BudgetModel,
    Cusum,
    RegressionSentinel,
)


def test_perf_regression_event_schema(tmp_path):
    sink = JsonlSink(str(tmp_path / "m.jsonl"))
    good = {
        "event": "perf_regression", "step": 7, "stream": "step_wall",
        "dominant": "compile",
        "components": {c: 0.0 for c in BUDGET_COMPONENTS},
        "residual_ms": 8.0, "expected_ms": 10.0, "measured_ms": 18.0,
        "plan_version": 2, "trace_id": "",
    }
    sink.emit(dict(good))
    # extra fields ride along (straggler_rank when the gang attributed one)
    sink.emit(dict(good, straggler_rank=3))
    # missing payload field and wrong types are rejected at the emit site
    bad = dict(good)
    del bad["dominant"]
    with pytest.raises(ValueError):
        sink.emit(bad)
    with pytest.raises(ValueError):
        sink.emit(dict(good, components="compile"))
    with pytest.raises(ValueError):
        sink.emit(dict(good, residual_ms="8"))
    sink.close()
    assert not validate_metrics_file(str(tmp_path / "m.jsonl"))


def test_budget_partition_sums_to_residual_with_all_components():
    model = BudgetModel(compute_ms=6.0, wire_ms=4.0)
    base_bytes = 1 << 20
    # feed the byte/host baselines with a few clean steps
    for step in range(5):
        model.settle(step, 10.0, host_ms=1.0, wire_bytes=base_bytes)
    model.note_compile(8.0)
    model.note_snapshot(6.0)
    model.note_backpressure(0.002)
    model.note_straggler(3.0, rank=2)
    budget = model.settle(5, 40.0, host_ms=2.5, wire_bytes=base_bytes * 2)
    assert set(budget.components) == set(BUDGET_COMPONENTS)
    assert budget.expected_ms == pytest.approx(10.0)
    assert budget.residual_ms == pytest.approx(30.0)
    assert budget.components["compile"] == pytest.approx(8.0)
    assert budget.components["snapshot"] == pytest.approx(6.0)
    assert budget.components["backpressure"] == pytest.approx(2.0)
    assert budget.components["straggler"] == pytest.approx(3.0)
    # 2x bytes = 1x excess over baseline, priced at wire_ms
    assert budget.components["wire_slowdown"] == pytest.approx(4.0)
    assert budget.components["host_data"] == pytest.approx(1.5)
    # the partition is exact by construction: unattributed is the remainder
    assert budget.partition_error_ms() == pytest.approx(0.0, abs=1e-9)
    assert sum(budget.components.values()) == pytest.approx(30.0)
    assert budget.dominant == "compile"
    assert budget.straggler_rank == 2
    # evidence hooks cleared: the next step settles clean
    nxt = model.settle(6, 10.0, host_ms=1.0, wire_bytes=base_bytes)
    assert nxt.components["compile"] == 0.0
    assert nxt.residual_ms == pytest.approx(0.0)


def test_budget_self_calibration_holds_fire_then_prices_the_median():
    model = BudgetModel(calibrate_steps=5)
    # while calibrating: expected = measured, residual 0, not calibrated
    early = model.settle(0, 50.0)
    assert early.residual_ms == 0.0 and not early.calibrated
    for step in range(1, 6):
        model.settle(step, 10.0 + step * 0.01)
    assert model.calibrated
    budget = model.settle(9, 20.0)
    assert budget.calibrated
    assert budget.expected_ms == pytest.approx(10.03, abs=0.5)
    assert budget.residual_ms == pytest.approx(10.0, abs=0.6)
    # a regressed step must NOT feed the baseline (no chasing)
    assert model.expected() == pytest.approx(10.03, abs=0.5)


def test_cusum_trips_on_sustained_shift_not_jitter():
    quiet = Cusum(k=1.0, h=8.0, warmup=10, alpha=0.05)
    rng = np.random.RandomState(0)
    assert not any(quiet.update(10.0 + rng.uniform(-0.1, 0.1))
                   for _ in range(300))
    shifted = Cusum(k=1.0, h=8.0, warmup=10, alpha=0.05)
    for _ in range(50):
        shifted.update(10.0 + rng.uniform(-0.1, 0.1))
    tripped = any(shifted.update(12.0 + rng.uniform(-0.1, 0.1))
                  for _ in range(50))
    assert tripped and shifted.trips == 1
    # goodput direction: a DOWNWARD shift trips the direction=-1 detector
    down = Cusum(k=1.0, h=8.0, warmup=10, alpha=0.05, direction=-1)
    for _ in range(50):
        down.update(0.9 + rng.uniform(-0.005, 0.005))
    assert any(down.update(0.7) for _ in range(50))


def test_sentinel_trips_attributes_and_drains(tmp_path):
    sink = JsonlSink(str(tmp_path / "m.jsonl"))
    registry = MetricsRegistry()
    sentinel = RegressionSentinel(
        budget=BudgetModel(compute_ms=6.0, wire_ms=4.0), sink=sink,
        registry=registry, warmup=10, threshold=8.0, cooldown=5, window=10,
    )
    sentinel.plan_version = 3
    rng = np.random.RandomState(0)
    step = 0
    for _ in range(20):
        sentinel.observe_step(step, 10.0 + float(rng.uniform(-0.05, 0.05)))
        step += 1
    assert not sentinel.incidents
    while not sentinel.incidents:
        sentinel.note_compile(8.0)
        sentinel.observe_step(step, 18.0 + float(rng.uniform(-0.05, 0.05)),
                              trace_id="00000000000000000000000000000abc")
        step += 1
        assert step < 100, "sentinel never tripped"
    inc = sentinel.incidents[0]
    assert inc["event"] == "perf_regression"
    assert inc["stream"] == "step_wall"
    assert inc["dominant"] == "compile"
    assert inc["plan_version"] == 3
    assert inc["trace_id"] == "00000000000000000000000000000abc"
    assert abs(sum(inc["components"].values()) - inc["residual_ms"]) <= (
        0.01 * max(1.0, abs(inc["residual_ms"]))
    )
    # the JSONL twin validated on emit; the counter ticked
    sink.close()
    assert not validate_metrics_file(str(tmp_path / "m.jsonl"))
    with open(str(tmp_path / "m.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    assert [e["event"] for e in events] == ["perf_regression"]
    assert registry.counter("perf_regressions_total").value == 1
    # drain hands over each incident exactly once
    assert sentinel.drain_incidents() == [inc]
    assert sentinel.drain_incidents() == []
    # cooldown re-arms: the sustained regression trips again eventually
    for _ in range(40):
        sentinel.note_compile(8.0)
        sentinel.observe_step(step, 18.0 + float(rng.uniform(-0.05, 0.05)))
        step += 1
    assert len(sentinel.incidents) >= 2
    assert sentinel.report()["wall_trips"] >= 2


def test_telemetry_regression_env_gate_and_budget_gauges(tmp_path, monkeypatch):
    # default off: the hub carries no sentinel
    assert Telemetry(flight=None).regression is None
    monkeypatch.setenv("BAGUA_REGRESSION_SENTINEL", "1")
    monkeypatch.setenv("BAGUA_REGRESSION_WARMUP", "5")
    path = str(tmp_path / "m.jsonl")
    tel = Telemetry(metrics_jsonl=path, flight=None)
    assert tel.regression is not None
    # the hub adopted its own sink + registry for the sentinel
    assert tel.regression.sink is tel.jsonl
    assert tel.regression.registry is tel.registry
    for step in range(8):
        tel.on_step(step, wall_s=0.010, n_samples=32, wire_bytes=1 << 16,
                    host_overhead={"pre": 0.001, "post": 0.001})
    snap = tel.snapshot()
    assert snap["regression"]["steps_seen"] == 8
    assert snap["regression"]["incidents"] == 0
    prom = tel.registry.to_prometheus()
    for comp in BUDGET_COMPONENTS:
        assert f"bagua_step_budget_{comp}_ms" in prom
    assert "bagua_step_budget_expected_ms" in prom
    assert "bagua_step_budget_residual_ms" in prom
    tel.close()
    assert not validate_metrics_file(path)
    # explicit instance wins over the env gate
    monkeypatch.delenv("BAGUA_REGRESSION_SENTINEL")
    sentinel = RegressionSentinel()
    tel2 = Telemetry(flight=None, regression=sentinel)
    assert tel2.regression is sentinel
    tel2.close()


def test_telemetry_feeds_sentinel_evidence_hooks(tmp_path):
    sentinel = RegressionSentinel(budget=BudgetModel(compute_ms=6.0))
    tel = Telemetry(flight=None, regression=sentinel)
    tel.on_compile_done("full", step=0, wall_ms=123.0)
    tel.on_snapshot(step=0, wall_ms=50.0, n_bytes=100, kind="final")
    tel.on_snapshot(step=0, wall_ms=999.0, n_bytes=100, kind="async")
    tel.on_rpc_retry("/rdzv/kv/x", attempt=1, delay_s=0.004,
                     reason="backpressure")
    budget = sentinel.budget
    assert budget._compile_ms == pytest.approx(123.0)
    # only BLOCKING snapshots stall the step; async writes cost nothing
    assert budget._snapshot_ms == pytest.approx(50.0)
    assert budget._backpressure_s == pytest.approx(0.004)
    tel.on_rebucket(plan_version=7, n_buckets=3)
    assert sentinel.plan_version == 7
    tel.close()

# -- per-axis wire attribution ------------------------------------------------


def test_perf_regression_axis_fields_ride_schema(tmp_path):
    """An axis-scoped incident (axis, link_class, wire_axis_ms) is the same
    schema event with extra fields — it must validate as-is so every
    downstream consumer (fleet push, diagnose_hang, perf_doctor) can read
    the axis without a schema bump."""
    sink = JsonlSink(str(tmp_path / "m.jsonl"))
    good = {
        "event": "perf_regression", "step": 7, "stream": "wire_axis:tp",
        "dominant": "wire_slowdown",
        "components": {c: 0.0 for c in BUDGET_COMPONENTS},
        "residual_ms": 8.0, "expected_ms": 10.0, "measured_ms": 18.0,
        "plan_version": 2, "trace_id": "",
    }
    sink.emit(dict(good, axis="tp", link_class="ici",
                   wire_axis_ms={"dp": 0.2, "tp": 7.8}))
    sink.close()
    assert not validate_metrics_file(str(tmp_path / "m.jsonl"))
    with open(str(tmp_path / "m.jsonl")) as f:
        (ev,) = [json.loads(line) for line in f if line.strip()]
    assert ev["axis"] == "tp" and ev["link_class"] == "ici"
    assert ev["wire_axis_ms"] == {"dp": 0.2, "tp": 7.8}


def test_budget_axis_partition_exact_on_every_pricing_path():
    """The per-axis wire split sums BITWISE to components["wire_slowdown"]
    on all three pricing paths (measured-by-axis, scalar-measured split by
    expected share, per-axis byte census) — partition by construction, not
    by tolerance."""
    axis_promise = {"dp": 3.0, "tp": 1.0}

    # path 1: per-axis measured wire — each axis's overshoot of its own
    # promise, the scalar defined as the sorted-key sum
    model = BudgetModel(compute_ms=6.0, axis_wire_ms=dict(axis_promise))
    assert model.wire_ms == 4.0  # the scalar promise IS the ledger's sum
    model.note_wire(9.2, by_axis={"dp": 7.3, "tp": 1.9})
    budget = model.settle(0, 16.0)
    assert budget.wire_axis_ms == pytest.approx({"dp": 4.3, "tp": 0.9})
    assert budget.components["wire_slowdown"] == (
        budget.wire_axis_ms["dp"] + budget.wire_axis_ms["tp"]
    )
    assert budget.axis_partition_error_ms() == 0.0
    assert budget.partition_error_ms() == pytest.approx(0.0, abs=1e-12)

    # path 2: scalar measured wire — proportional split by expected share,
    # the last (sorted) axis takes the exact remainder
    model.note_wire(9.0)
    budget = model.settle(1, 15.0)
    assert set(budget.wire_axis_ms) == {"dp", "tp"}
    assert budget.components["wire_slowdown"] == 5.0
    assert budget.wire_axis_ms["dp"] == pytest.approx(5.0 * 3.0 / 4.0)
    assert (budget.wire_axis_ms["dp"] + budget.wire_axis_ms["tp"]) == 5.0
    assert budget.axis_partition_error_ms() == 0.0

    # path 3: per-axis byte census — each axis's excess priced on its own
    # leg (here the ledger fallback), the scalar the sum of the parts
    census = BudgetModel(compute_ms=6.0, axis_wire_ms=dict(axis_promise))
    for step in range(5):
        census.settle(step, 10.0,
                      wire_bytes_by_axis={"dp": 1 << 20, "tp": 1 << 18})
    budget = census.settle(5, 14.0,
                           wire_bytes_by_axis={"dp": 1 << 21, "tp": 1 << 18})
    # dp doubled its bytes (1x excess over baseline, priced at its 3.0 ms
    # promise); tp stayed on baseline
    assert budget.wire_axis_ms["dp"] == pytest.approx(3.0)
    assert budget.wire_axis_ms["tp"] == 0.0
    assert budget.components["wire_slowdown"] == (
        budget.wire_axis_ms["tp"] + budget.wire_axis_ms["dp"]
    )
    assert budget.axis_partition_error_ms() == 0.0

    # axis-blind model: empty split, legacy scalar behavior unchanged
    legacy = BudgetModel(compute_ms=6.0, wire_ms=4.0)
    legacy.note_wire(9.0)
    budget = legacy.settle(0, 15.0)
    assert budget.wire_axis_ms == {}
    assert budget.components["wire_slowdown"] == 5.0
    assert budget.axis_partition_error_ms() == 0.0
    assert "wire_axis_ms" in budget.payload()


def test_budget_priced_axis_ledger_from_program_and_cost_model():
    """BudgetModel(program=...) joins the flight/IR records' ``axes``
    against the planner's per-axis α–β legs; a joint multi-axis record
    splits its bytes evenly across its axes, and axis-blind records are
    ignored."""
    from bagua_tpu.observability.attribution import priced_axis_wire_ms
    from bagua_tpu.service.planner import AlphaBeta, CostModel

    cm = CostModel(
        flat=AlphaBeta(0.0, 1e9),
        axis_legs={"dp": AlphaBeta(0.0, 1e8), "tp": AlphaBeta(0.0, 1e9)},
    )
    program = [
        {"algo": "gradient_allreduce", "bucket": 0, "nbytes": 1 << 20,
         "axes": ["dp"]},
        {"algo": "gradient_allreduce", "bucket": 1, "nbytes": 1 << 21,
         "axes": ["dp", "tp"]},  # joint exchange: bytes split evenly
        {"algo": "zero", "bucket": 0, "nbytes": 1 << 20},  # axis-blind
    ]
    ledger = priced_axis_wire_ms(cm, program)
    dp_bytes = (1 << 20) + (1 << 20)  # own record + half the joint one
    assert ledger["dp"] == pytest.approx(dp_bytes / 1e8 * 1e3)
    assert ledger["tp"] == pytest.approx((1 << 20) / 1e9 * 1e3)

    model = BudgetModel(compute_ms=6.0, cost_model=cm, program=program)
    assert model.axis_wire_ms == ledger
    # the scalar wire promise is the sorted-key sum of the ledger — bitwise
    assert model.wire_ms == ledger["dp"] + ledger["tp"]
    # no axes anywhere -> no ledger, wire stays unpriced
    blind = BudgetModel(compute_ms=6.0, cost_model=cm,
                        program=[{"algo": "zero", "bucket": 0,
                                  "nbytes": 1 << 20}])
    assert blind.axis_wire_ms == {} and blind.wire_ms is None


def test_sentinel_per_axis_stream_trips_and_names_link_class(tmp_path):
    """A sustained single-axis wire drift (wall flat: the collapse hides
    inside overlap slack) trips that axis's own CUSUM stream; the incident
    names the axis and resolves its physical link class (tp -> ici)."""
    sink = JsonlSink(str(tmp_path / "m.jsonl"))
    sentinel = RegressionSentinel(
        budget=BudgetModel(compute_ms=6.0,
                           axis_wire_ms={"dp": 3.0, "tp": 1.0}),
        sink=sink, warmup=10, threshold=8.0, cooldown=5, window=10,
    )
    step = 0
    for _ in range(20):
        sentinel.note_wire(4.0, by_axis={"dp": 3.0, "tp": 1.0})
        sentinel.observe_step(step, 10.0)
        step += 1
    assert not sentinel.incidents
    while not sentinel.incidents:
        # tp browns out; the wall stays flat so only the axis stream sees it
        sentinel.note_wire(10.0, by_axis={"dp": 3.0, "tp": 7.0})
        sentinel.observe_step(step, 10.0)
        step += 1
        assert step < 100, "axis stream never tripped"
    inc = sentinel.incidents[0]
    assert inc["stream"] == "wire_axis:tp"
    assert inc["axis"] == "tp" and inc["link_class"] == "ici"
    assert inc["wire_axis_ms"]["tp"] > inc["wire_axis_ms"]["dp"]
    assert sentinel.report()["axis_trips"]["tp"] >= 1
    sink.close()
    assert not validate_metrics_file(str(tmp_path / "m.jsonl"))

    # a committed config change resets the per-axis detectors and can
    # re-price the ledger alongside the scalar promise
    sentinel.rebaseline(wire_ms=2.0, axis_wire_ms={"dp": 1.5, "tp": 0.5})
    assert sentinel._axis_cusums == {}
    assert sentinel.budget.wire_ms == 2.0
    assert sentinel.budget.axis_wire_ms == {"dp": 1.5, "tp": 0.5}


def test_sentinel_wall_trip_indicts_dominant_axis():
    """A wall-stream trip whose verdict is wire-dominant picks the axis
    with the largest windowed slowdown (dp -> dcn link class)."""
    sentinel = RegressionSentinel(
        budget=BudgetModel(compute_ms=6.0,
                           axis_wire_ms={"dp": 3.0, "tp": 1.0}),
        warmup=10, threshold=8.0, cooldown=5, window=10,
    )
    step = 0
    for _ in range(20):
        sentinel.note_wire(4.0, by_axis={"dp": 3.0, "tp": 1.0})
        sentinel.observe_step(step, 10.0)
        step += 1
    while not sentinel.incidents:
        sentinel.note_wire(12.0, by_axis={"dp": 11.0, "tp": 1.0})
        sentinel.observe_step(step, 18.0)
        step += 1
        assert step < 100, "sentinel never tripped"
    inc = sentinel.incidents[0]
    assert inc["dominant"] == "wire_slowdown"
    assert inc["axis"] == "dp" and inc["link_class"] == "dcn"
    # incident-level partition: the axis split sums to the windowed
    # wire_slowdown component up to the payload rounding
    assert sum(inc["wire_axis_ms"].values()) == pytest.approx(
        inc["components"]["wire_slowdown"], abs=1e-2)


def test_telemetry_exports_per_axis_counters_and_gauges(tmp_path, monkeypatch):
    monkeypatch.setenv("BAGUA_REGRESSION_SENTINEL", "1")
    monkeypatch.setenv("BAGUA_REGRESSION_WARMUP", "5")
    path = str(tmp_path / "m.jsonl")
    tel = Telemetry(metrics_jsonl=path, flight=None)
    for step in range(6):
        tel.on_step(step, wall_s=0.010, n_samples=32, wire_bytes=3 << 16,
                    wire_bytes_by_axis={"dp": 1 << 17, "tp": 1 << 16})
    prom = tel.registry.to_prometheus()
    assert "bagua_wire_bytes_axis_dp_total" in prom
    assert "bagua_wire_bytes_axis_tp_total" in prom
    assert "bagua_step_budget_wire_dp_ms" in prom
    assert "bagua_step_budget_wire_tp_ms" in prom
    tel.close()
    assert not validate_metrics_file(path)
    with open(path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    steps = [e for e in events if e.get("event") == "step"]
    assert steps and steps[-1]["wire_bytes_by_axis"] == {
        "dp": 1 << 17, "tp": 1 << 16,
    }
