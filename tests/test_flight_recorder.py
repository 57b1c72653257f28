"""Collective flight recorder: ring semantics, dump atomicity, the engine's
trace-time capture / dispatch-time replay, and the cross-rank hang join.

The recorder's three contracts, each pinned here:

* **ring safety** — wraparound keeps the newest ``capacity`` records in
  sequence order, and a dump racing a concurrent ``record()`` (the
  watchdog thread vs the dispatch thread) never observes a torn record;
* **bitwise-inert** — a DDP engine with the recorder attached trains to
  *bit-identical* params + optimizer state vs recorder-off, for both
  gradient_allreduce and zero with overlap on (capture reads trace-time
  Python values only, replay happens on the host);
* **forensics** — per-rank dumps validate against ``bagua.flight_dump.v1``
  and :func:`build_hang_report` joins them into the documented verdict
  taxonomy (healthy / desync / straggler / host_wedge / no_data) with
  first-divergence and blocked-on attribution.
"""

import hashlib
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms import build_algorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import Telemetry, Watchdog, validate_metrics_file
from bagua_tpu.observability.flight_recorder import (
    FLIGHT_DUMP_SCHEMA,
    FlightRecorder,
    build_hang_report,
    capture_program,
    flight_dump_path,
    notify_collective,
    notify_ring,
    push_flight_digest,
    validate_flight_dump,
    validate_flight_record,
    validate_hang_report,
)

LAYERS = [12, 16, 16, 4]


def make_record(seq_hint=0, bucket=0, phase="overlap", step=0, label=None):
    """A schema-complete record template (``record_program`` stamps seq/
    step/timestamps on replay; here we stamp them by hand)."""
    return {
        "step": step,
        "label": label or f"bagua_ex/algo=gradient_allreduce/bucket={bucket}/phase={phase}",
        "algo": "gradient_allreduce",
        "bucket": bucket,
        "phase": phase,
        "precision": "f32",
        "nbytes": 4096,
        "plan_version": 1,
        "t_enqueue": 100.0 + seq_hint,
        "t_retire": 100.5 + seq_hint,
    }


def fill(recorder, n_records, step=0, retired=True):
    program = [make_record(i, bucket=i % 3, step=step) for i in range(n_records)]
    for rec in program:
        if not retired:
            rec["t_retire"] = None
        recorder.record(rec)


# -- ring semantics -----------------------------------------------------------


def test_ring_wraparound_keeps_newest_in_order():
    fr = FlightRecorder(capacity=16)
    for i in range(16 + 5):
        fr.record(make_record(i))
    recs = fr.records()
    assert len(recs) == 16  # the oldest 5 evicted
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(5, 21))  # newest capacity records, in order
    assert fr.last_seq == 20


def test_retire_stamps_only_live_matching_records():
    fr = FlightRecorder(capacity=8)
    seqs = fr.record_program([make_record(0), make_record(1)], step=3)
    recs = fr.records()
    assert [r["t_retire"] for r in recs] == [None, None]
    assert [r["step"] for r in recs] == [3, 3]
    fr.retire(seqs)
    assert all(r["t_retire"] is not None for r in fr.records())
    # a seq the ring has since evicted is skipped, not resurrected
    for i in range(10):
        fr.record(make_record(i))
    fr.retire(seqs)  # stale: slots now hold newer seqs
    assert all(r["seq"] >= 4 for r in fr.records())


def test_concurrent_record_and_dump_never_torn(tmp_path):
    """The watchdog-thread dump racing the dispatch-thread append: every
    record the dump sees must be complete and schema-valid, with strictly
    increasing seqs — a torn (half-built) record would fail validation."""
    fr = FlightRecorder(capacity=64, rank=0, world_size=1)
    stop = threading.Event()
    errors = []

    def writer():
        step = 0
        while not stop.is_set():
            seqs = fr.record_program(
                [make_record(i, bucket=i) for i in range(4)], step=step
            )
            fr.retire(seqs)
            step += 1

    def reader():
        while not stop.is_set():
            recs = fr.records()
            seqs = [r["seq"] for r in recs]
            if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
                errors.append(f"non-monotonic snapshot: {seqs}")
                return
            for r in recs:
                problems = validate_flight_record(r)
                if problems:
                    errors.append(f"torn record: {problems}")
                    return
            dump = fr.dump(str(tmp_path / "flight_0.json"), reason="race")
            problems = validate_flight_dump(dump)
            # the in-memory payload must always validate; last_seq advances
            # between records() and the payload build, so only tears count
            problems = [p for p in problems if "last_seq" not in p]
            if problems:
                errors.append(f"torn dump: {problems}")
                return

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start(), r.start()
    import time as _time

    _time.sleep(0.5)
    stop.set()
    w.join(5.0), r.join(5.0)
    assert not errors, errors
    assert fr.last_seq > 100  # the race actually exercised wraparound


def test_dump_roundtrip_validates(tmp_path):
    fr = FlightRecorder(capacity=32, rank=2, world_size=4)
    fill(fr, 10)
    path = flight_dump_path(str(tmp_path), fr.rank)
    assert path.endswith("flight_2.json")
    fr.dump(path, reason="manual", telemetry={"step": 9, "phase": "wait"},
            plan_version=1)
    with open(path) as f:
        dump = json.load(f)
    assert validate_flight_dump(dump) == []
    assert dump["schema"] == FLIGHT_DUMP_SCHEMA
    assert dump["rank"] == 2 and dump["world_size"] == 4
    assert dump["reason"] == "manual"
    assert len(dump["records"]) == 10 and dump["last_seq"] == 9
    assert dump["threads"]  # every live thread's stack rides along
    assert dump["telemetry"]["phase"] == "wait"
    # no temp file left behind (write-temp + os.replace)
    assert [p.name for p in tmp_path.iterdir()] == ["flight_2.json"]


def test_validators_reject_malformed(tmp_path):
    fr = FlightRecorder(capacity=8)
    fill(fr, 3)
    dump = fr.dump(str(tmp_path / "d.json"), reason="x")
    assert validate_flight_dump(dump) == []
    bad = dict(dump, schema="bogus")
    assert any("schema" in p for p in validate_flight_dump(bad))
    bad = dict(dump)
    bad["records"] = [dict(dump["records"][0])]
    del bad["records"][0]["bucket"]
    assert any("bucket" in p for p in validate_flight_dump(bad))
    report = build_hang_report([dump])
    assert validate_hang_report(report) == []
    assert any("verdict" in p
               for p in validate_hang_report(dict(report, verdict="nope")))


# -- trace-time capture -------------------------------------------------------


def test_capture_program_collects_and_restores():
    notify_collective("gradient_allreduce", 0, "mono")  # no capture: no-op
    with capture_program() as events:
        notify_collective("gradient_allreduce", 0, "overlap")
        notify_ring(kind="rs", bits=8, hops=7, wire_bytes=1024)
        with capture_program() as inner:  # reentrant
            notify_collective("zero", 1, "rs")
        notify_collective("gradient_allreduce", 1, "overlap")
    notify_collective("gradient_allreduce", 9, "mono")  # capture over: no-op
    assert [e["phase"] for e in events] == ["overlap", "hop", "overlap"]
    assert inner == [{"algo": "zero", "bucket": 1, "phase": "rs"}]
    hop = events[1]
    # the ring hop inherits the enclosing collective's attribution and
    # carries the hop count in-record
    assert hop["algo"] == "gradient_allreduce" and hop["bucket"] == 0
    assert hop["hops"] == 7 and hop["precision"] == "int8"
    assert hop["nbytes"] == 1024


# -- the cross-rank join ------------------------------------------------------


def rank_dump(tmp_path, rank, n_records, *, drop_idx=None, unretired_from=None,
              phase="wait", world_size=4, axes=None):
    fr = FlightRecorder(capacity=64, rank=rank, world_size=world_size)
    program = [make_record(i, bucket=i % 3, step=i // 3) for i in range(n_records)]
    if axes is not None:  # named-mesh engines stamp the exchange axes
        program = [dict(rec, axes=list(axes)) for rec in program]
    if drop_idx is not None:
        program = program[:drop_idx] + program[drop_idx + 1:]
    for i, rec in enumerate(program):
        if unretired_from is not None and i >= unretired_from:
            rec = dict(rec, t_retire=None)
        fr.record(rec)
    return fr.dump(flight_dump_path(str(tmp_path), rank),
                   reason="watchdog_timeout",
                   telemetry={"step": n_records // 3, "phase": phase})


def test_hang_report_healthy_and_no_data(tmp_path):
    report = build_hang_report([])
    assert report["verdict"] == "no_data"
    dumps = [rank_dump(tmp_path, r, 12) for r in range(4)]
    report = build_hang_report(dumps)
    assert validate_hang_report(report) == []
    assert report["verdict"] == "healthy"
    assert report["lagging_ranks"] == [] and report["divergent_ranks"] == []


def test_hang_report_first_desync_attribution(tmp_path):
    """One rank skipped a collective mid-stream: the join must name the
    first divergent seq, the minority rank, and the majority's record as
    the collective the gang desynced at."""
    dumps = [rank_dump(tmp_path, r, 12, drop_idx=7 if r == 2 else None)
             for r in range(4)]
    report = build_hang_report(dumps)
    assert validate_hang_report(report) == []
    assert report["verdict"] == "desync"
    assert report["first_divergence_seq"] == 7
    assert report["divergent_ranks"] == [2]
    blocked = report["blocked_on"]
    assert blocked["seq"] == 7 and blocked["bucket"] == 7 % 3
    assert blocked["label"].endswith(f"bucket={7 % 3}/phase=overlap")
    assert blocked["plan_version"] == 1


def test_hang_report_straggler_vs_host_wedge(tmp_path):
    # identical programs, rank 1 stopped 3 records early with everything
    # retired and the host parked in "wait": a device-side straggler
    dumps = [rank_dump(tmp_path, r, 9 if r == 1 else 12) for r in range(4)]
    report = build_hang_report(dumps)
    assert report["verdict"] == "straggler"
    assert report["lagging_ranks"] == [1]
    # blocked_on = the first collective rank 1 never issued (seq 9), read
    # from an advanced rank's ring
    assert report["blocked_on"]["seq"] == 9
    assert report["per_rank"]["1"]["unretired"] == 0

    # same lag, but the laggard never came back from its last dispatch
    # (unretired records) => the host is wedged, not the device
    dumps = [rank_dump(tmp_path, r, 9 if r == 1 else 12,
                       unretired_from=8 if r == 1 else None,
                       phase="dispatch" if r == 1 else "wait")
             for r in range(4)]
    report = build_hang_report(dumps)
    assert report["verdict"] == "host_wedge"
    assert report["per_rank"]["1"]["unretired"] == 1
    assert report["blocked_on"]["seq"] == 9


def test_hang_report_blocked_on_carries_axes(tmp_path):
    """On a named mesh the records carry the exchange axes; the straggler
    verdict's ``blocked_on`` must surface them (which link a wedged gang is
    stuck behind), and the diagnose_hang summary must print them alongside
    any nearby axis-scoped sentinel incident."""
    import importlib.util
    import os

    dumps = [rank_dump(tmp_path, r, 9 if r == 1 else 12, axes=["dp", "fsdp"])
             for r in range(4)]
    report = build_hang_report(dumps)
    assert validate_hang_report(report) == []
    assert report["verdict"] == "straggler"
    assert report["blocked_on"]["axes"] == ["dp", "fsdp"]
    # axis-blind dumps keep the legacy shape: no axes key at all
    (tmp_path / "legacy").mkdir()
    legacy = build_hang_report(
        [rank_dump(tmp_path / "legacy", r, 9 if r == 1 else 12)
         for r in range(4)])
    assert "axes" not in legacy["blocked_on"]

    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ci", "diagnose_hang.py")
    spec = importlib.util.spec_from_file_location("_diagnose_hang", script)
    dh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dh)
    incident = {
        "event": "perf_regression", "ts": 1.0, "step": 30,
        "stream": "wire_axis:fsdp", "dominant": "wire_slowdown",
        "residual_ms": 9.0, "axis": "fsdp", "link_class": "dcn",
    }
    dh.fold_incidents(report, [incident])
    assert report["incidents"][-1]["axis"] == "fsdp"
    assert report["incidents"][-1]["link_class"] == "dcn"
    text = dh.summarize(report)
    assert "axes dpxfsdp" in text
    assert "axis fsdp [dcn]" in text


# -- the engine integration ---------------------------------------------------


def make_batch(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32))
    y = jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32))
    return x, y


def run_steps(group, algo_name, flight, steps=3, overlap=True, hub=True, monitor=None):
    tel = Telemetry(flight=flight) if hub else None
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.1, momentum=0.9), build_algorithm(algo_name),
        process_group=group, bucket_size_bytes=1 << 9, overlap=overlap,
        telemetry=tel, health_monitor=monitor,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    losses = None
    for _ in range(steps):
        state, losses = ddp.train_step(state, batch)
    jax.block_until_ready(losses)
    ddp.drain_steps()
    ddp.shutdown()
    if tel is not None:
        tel.close()
    return ddp, state


def state_sha(state):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_ddp_capture_replays_one_record_per_collective(group):
    fr = FlightRecorder(capacity=128, rank=0, world_size=1)
    ddp, _ = run_steps(group, "gradient_allreduce", fr, steps=3)
    assert ddp.plan.num_buckets > 1
    program = ddp.flight_program()
    # the captured program: one overlap collective per plan bucket, in the
    # named-scope grammar, carrying plan bytes + version
    assert len(program) == ddp.plan.num_buckets
    # capture preserves *issue* order (backward-pass bucket order under
    # overlap), covering every plan bucket exactly once
    assert sorted(r["bucket"] for r in program) == list(range(ddp.plan.num_buckets))
    for rec in program:
        assert rec["phase"] == "overlap"
        assert rec["label"] == (
            f"bagua_ex/algo=gradient_allreduce/bucket={rec['bucket']}"
            f"/phase=overlap"
        )
        assert rec["nbytes"] == ddp.plan.specs[rec["bucket"]].nbytes > 0
        assert rec["plan_version"] == ddp.plan_version
    # every dispatch (3 steps) replayed the program and retired its records
    recs = fr.records()
    assert len(recs) == 3 * len(program)
    assert all(r["t_retire"] is not None for r in recs)
    assert [r["step"] for r in recs[:len(program)]] == [0] * len(program)
    assert recs[-1]["step"] == 2


@pytest.mark.parametrize("algo_name", ["gradient_allreduce", "zero"])
def test_recorder_is_bitwise_inert(group, algo_name):
    """The acceptance criterion: recorder on vs off trains bit-identical
    state (params + optimizer), overlap on, for the all-reduce AND the
    sharded (zero) exchange paths."""
    _, state_off = run_steps(group, algo_name, None, steps=3)
    fr = FlightRecorder(capacity=128, rank=0, world_size=1)
    _, state_on = run_steps(group, algo_name, fr, steps=3)
    assert fr.last_seq >= 0  # the recorder actually recorded
    assert state_sha(state_on) == state_sha(state_off)


@pytest.mark.parametrize("algo_name", ["gradient_allreduce", "zero"])
def test_hub_waiter_and_monitor_are_bitwise_inert(group, algo_name):
    """The same pin for the whole of the operator's tracing: a bare engine
    against one with the hub (its waiter thread holding every step's losses
    and health vector), the recorder and a health monitor that reads a step
    late."""
    from bagua_tpu.observability import HealthMonitor

    _, state_bare = run_steps(group, algo_name, None, steps=4, hub=False)
    fr = FlightRecorder(capacity=128, rank=0, world_size=1)
    monitor = HealthMonitor()
    ddp, state_on = run_steps(group, algo_name, fr, steps=4, monitor=monitor)
    assert fr.last_seq >= 0 and monitor.report()["observed_steps"] == 4
    assert ddp.telemetry.snapshot()["completed_step"] == 3
    assert state_sha(state_on) == state_sha(state_bare)


def test_quantized_ring_records_hops(group, monkeypatch):
    """The int8 wire path records one phase="hop" descriptor per ring leg
    with the hop count in-record, attributed to its bucket."""
    monkeypatch.setenv("BAGUA_QR_BLOCK", "128")
    fr = FlightRecorder(capacity=256, rank=0, world_size=1)
    tel = Telemetry(flight=fr)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.1),
        build_algorithm("gradient_allreduce", wire_precision="int8"),
        process_group=group, bucket_size_bytes=1 << 9, telemetry=tel,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    state, losses = ddp.train_step(state, make_batch())
    jax.block_until_ready(losses)
    ddp.shutdown()
    program = ddp.flight_program()
    hops = [r for r in program if r["phase"] == "hop"]
    n = ddp.group.size
    assert hops, "quantized ring left no hop records"
    assert {r["ring"] for r in hops} == {"rs", "ag"}
    for rec in hops:
        assert rec["hops"] == n - 1
        assert rec["precision"] == "int8" and rec["nbytes"] > 0
        assert rec["bucket"] >= 0  # inherited from the enclosing collective


# -- the dying path -----------------------------------------------------------


def test_watchdog_timeout_leaves_evidence_and_hang_event(tmp_path):
    """Satellite 1 + the dump hooks: a watchdog timeout atomically writes
    watchdog_dump.json and flight_<rank>.json, pushes the digest, and emits
    a schema-valid ``hang`` JSONL event through the hub — all BEFORE
    on_timeout runs."""
    events_path = str(tmp_path / "metrics.jsonl")
    fr = FlightRecorder(capacity=32, rank=0, world_size=1)
    fill(fr, 5, step=7)
    tel = Telemetry(metrics_jsonl=events_path, flight=fr)
    tel.current_step, tel.current_phase = 7, "dispatch"
    order = []
    pushed = []
    wd = Watchdog(timeout_s=0.15, check_interval_s=0.05,
                  on_timeout=lambda s: order.append("on_timeout"))
    wd.dump_dir = str(tmp_path)
    wd.digest_pusher = lambda: pushed.append(True)
    tel.bind_watchdog(wd)
    assert wd.flight_recorder is fr and wd.hang_hook == tel.on_hang
    wd.start()
    wd.beat(phase="dispatch")
    import time as _time

    deadline = _time.time() + 3.0
    while not order and _time.time() < deadline:
        _time.sleep(0.05)
    wd.stop()
    tel.close()
    assert order == ["on_timeout"]
    assert pushed  # digest pusher ran on the dying path

    with open(tmp_path / "watchdog_dump.json") as f:
        wdump = json.load(f)
    assert wdump["reason"] == "watchdog_timeout"
    assert wdump["last_phase"] == "dispatch"
    assert wdump["telemetry"]["step"] == 7
    with open(tmp_path / "flight_0.json") as f:
        fdump = json.load(f)
    assert validate_flight_dump(fdump) == []
    assert fdump["reason"] == "watchdog_timeout" and len(fdump["records"]) == 5

    assert validate_metrics_file(events_path) == []
    with open(events_path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    hang = [e for e in events if e["event"] == "hang"]
    assert len(hang) == 1
    assert hang[0]["reason"] == "watchdog_timeout"
    assert hang[0]["last_phase"] == "dispatch"
    assert hang[0]["flight_last_seq"] == 4
    assert "watchdog_dump" in hang[0]["dumps"] and "flight_dump" in hang[0]["dumps"]


def test_push_flight_digest_best_effort():
    fr = FlightRecorder(capacity=8, rank=3, world_size=4)
    fill(fr, 4)

    class KV:
        def __init__(self):
            self.store = {}

        def kv_set(self, key, value):
            self.store[key] = value

    class Breaker:
        def before_call(self):
            pass

        def record_success(self):
            pass

        def record_failure(self):
            pass

    kv = KV()
    assert push_flight_digest(kv, fr, attempt="a1", breaker=Breaker())
    digest = kv.store["bagua/flight/a1/rank3"]
    assert digest["rank"] == 3 and digest["last_seq"] == 3
    assert digest["unretired"] == 0
    assert digest["last"]["seq"] == 3

    class DeadKV:
        def kv_set(self, key, value):
            raise OSError("kv down")

    # outage: degrade to local-only, never raise
    assert push_flight_digest(DeadKV(), fr, attempt="a1", breaker=Breaker()) is False
    assert push_flight_digest(None, fr) is False
    assert push_flight_digest(kv, None) is False
