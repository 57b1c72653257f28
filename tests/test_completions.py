"""The hub sees its own steps complete (``observability/completions.py``).

Pins: ``Trainer`` builds hub and monitor from plain values; without a hub
there is no waiter thread, no collector hook, no ``completions`` entry and
the counters of before; with one, ``step_wall_ms`` is the interval between
completions and not the dispatch's wall; health is observed when the step is
done, with the step it is about, none lost and none twice, and before
``train_step`` returns where an action is registered; a stall is one
schema-valid event whose phases sum to its interval; ``close()`` joins the
waiter and removes the hook.
"""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms import Algorithm, build_algorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import (
    HealthConfig,
    HealthMonitor,
    Telemetry,
    completions,
    validate_metrics_event,
    validate_metrics_file,
)
from bagua_tpu.observability.flight_recorder import FlightRecorder, validate_flight_dump
from bagua_tpu.trainer import Trainer

LAYERS = [12, 16, 4]
WAITER = "bagua-step-waiter"
#: the counters an engine with neither hub nor monitor has had since PR 26
BARE_KEYS = {"pre", "lock_wait", "dispatch", "post", "build", "telemetry", "health",
             "next_batch", "loop", "steps"}


def make_batch(seed=0, nan=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(32, LAYERS[0]).astype(np.float32)
    if nan:
        x[0, 0] = np.nan
    return jnp.asarray(x), jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32))


def slow_loss(params, batch):
    """A step of some twenty milliseconds on the device whose dispatch
    returns at once (a host callback would make the dispatch wait)."""
    m = jnp.full((256, 256), 1e-3, jnp.float32)
    m = jax.lax.fori_loop(0, 60, lambda i, a: jnp.tanh(a @ a), m)
    return mse_loss(params, batch) + 0.0 * jnp.sum(m)


def make_ddp(group, loss=mse_loss, **kw):
    ddp = DistributedDataParallel(
        loss, optax.sgd(0.1), build_algorithm("gradient_allreduce"), process_group=group, **kw)
    return ddp, ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))


def waiters():
    return [t for t in threading.enumerate() if t.name == WAITER]


@pytest.fixture()
def one_device():
    """A group of one device: its dispatch returns before the step has run,
    as on a chip (across the eight simulated devices it does not)."""
    import bagua_tpu

    return bagua_tpu.init_process_group(devices=jax.devices()[:1])


# -- A: plain values ----------------------------------------------------------------


@pytest.mark.parametrize("telemetry, monitor", [
    (None, None), (True, True), (True, None), (None, True),
    ({"flight": None, "retrace_window": 7}, {"config": {"warmup_steps": 2}}),
    ("instance", "instance"),
], ids=["none", "true", "hub_alone", "monitor_alone", "dicts", "instances"])
def test_trainer_builds_hub_and_monitor_from_plain_values(group, telemetry, monitor):
    given_hub = Telemetry() if telemetry == "instance" else telemetry
    given_monitor = HealthMonitor() if monitor == "instance" else monitor
    trainer = Trainer(mse_loss, optax.sgd(0.1), Algorithm.init("gradient_allreduce"),
                      process_group=group, watchdog_timeout_s=0,
                      telemetry=given_hub, health_monitor=given_monitor)
    hub, mon = trainer.telemetry, trainer.health_monitor
    assert hub is trainer.ddp.telemetry and mon is trainer.ddp.health_monitor
    if telemetry is None:
        assert hub is None
    elif telemetry == "instance":
        assert hub is given_hub
    else:
        assert isinstance(hub, Telemetry) and hub.jsonl is None and hub.tracer is None
        assert hub.regression is None and hub.goodput is None
        if telemetry is True:
            assert isinstance(hub.flight, FlightRecorder)  # BAGUA_FLIGHT_RECORDER's default
        else:
            assert hub.flight is None and hub.recompile.window == 7
    if monitor is None:
        assert mon is None
    elif monitor == "instance":
        assert mon is given_monitor
    else:
        assert isinstance(mon, HealthMonitor) and mon.actions == []
        assert mon.telemetry is hub  # bound to the hub, where there is one
        assert mon.config == (HealthConfig() if monitor is True else HealthConfig(warmup_steps=2))
    trainer.close()
    if telemetry == "instance":
        given_hub.close()


def test_trainer_closes_the_hub_it_built_and_only_flushes_a_callers(group, tmp_path):
    def trained(telemetry):
        trainer = Trainer(mse_loss, optax.sgd(0.1), Algorithm.init("gradient_allreduce"),
                          process_group=group, watchdog_timeout_s=0, telemetry=telemetry)
        state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
        trainer.fit(state, [make_batch()] * 3, log_every=0)
        assert len(waiters()) == 1
        trainer.close()
        return trainer.telemetry

    built = trained({"metrics_jsonl": str(tmp_path / "built.jsonl")})
    assert waiters() == [] and built.jsonl._f is None  # closed: waiter joined, stream shut
    mine = trained(Telemetry(metrics_jsonl=str(tmp_path / "mine.jsonl")))
    assert mine.jsonl._f is not None and len(waiters()) == 1  # still the caller's to close
    mine.close()
    assert waiters() == []


# -- inert without a hub --------------------------------------------------------------


def test_without_a_hub_there_is_no_waiter_no_hook_and_no_new_counter(group):
    hooks = list(gc.callbacks)
    ddp, state = make_ddp(group)
    for _ in range(3):
        state, losses = ddp.train_step(state, make_batch())
    ddp.drain_steps()  # nothing to drain, and no error
    assert waiters() == [] and gc.callbacks == hooks
    assert set(ddp.host_overhead) == BARE_KEYS
    snapshot = ddp.host_overhead_snapshot()
    assert "completions" not in snapshot
    assert {k for k in snapshot if k.endswith("_ms_per_step")} == {
        f"{k}_ms_per_step" for k in BARE_KEYS - {"steps"}}
    ddp.shutdown()


def test_the_new_counters_exist_only_with_what_they_count(group):
    monitor_alone, _ = make_ddp(group, health_monitor=HealthMonitor())
    assert set(monitor_alone.host_overhead) == BARE_KEYS | {"health_wait"}
    hub_without_recorder, _ = make_ddp(group, telemetry=Telemetry(flight=None))
    assert set(hub_without_recorder.host_overhead) == BARE_KEYS | {"health_wait"}
    hub, _ = make_ddp(group, telemetry=Telemetry())
    assert set(hub.host_overhead) == BARE_KEYS | {"health_wait", "flight"}


# -- B: completions ---------------------------------------------------------------------


def test_step_wall_is_the_interval_between_completions_not_the_dispatch(one_device):
    group = one_device
    tel = Telemetry()
    ddp, state = make_ddp(group, loss=slow_loss, telemetry=tel)
    state, _ = ddp.train_step(state, make_batch())  # compiles
    ddp.drain_steps()
    ddp.host_overhead_snapshot(reset=True)
    began = time.perf_counter()
    for _ in range(6):
        state, _ = ddp.train_step(state, make_batch())
    dispatched = time.perf_counter() - began
    ddp.drain_steps()
    took = time.perf_counter() - began
    snapshot = ddp.host_overhead_snapshot()
    done = snapshot["completions"]
    assert done["steps"] == 6 and tel.snapshot()["completed_step"] == 6
    # the six intervals add up to the time the six steps took, which the
    # dispatches' walls (the engine's own ``step_wall_ms``) come nowhere near
    intervals = tel.registry.snapshot()["step_wall_ms"]
    assert intervals["count"] == 7
    assert done["interval_ms"]["p50"] > 5 * snapshot["step_wall_ms"]["p50"]
    assert 6 * done["interval_ms"]["p50"] > 0.5 * took * 1e3 > 2 * dispatched * 1e3
    assert done["interval_ms"]["max"] >= done["interval_ms"]["p95"] >= done["interval_ms"]["p50"]
    # and the gauge is samples over such an interval, not over a dispatch
    assert tel.registry.snapshot()["samples_per_s"] < 32 / (0.5 * took / 6)
    assert done["run_ahead_mean"] > 1  # nothing made the dispatch wait for the device
    assert tel.registry.snapshot()["steps_completed_total"] == 7
    # the reset clears the stretch
    ddp.host_overhead_snapshot(reset=True)
    assert ddp.host_overhead_snapshot()["completions"]["steps"] == 0
    assert ddp.host_overhead_snapshot()["completions"]["interval_ms"] == {}
    tel.close()
    ddp.shutdown()


def test_a_hub_fed_by_hand_files_the_wall_it_is_given():
    tel = Telemetry()
    for step in range(4):
        tel.on_step(step=step, wall_s=0.010, n_samples=32, wire_bytes=0)
    snapshot = tel.registry.snapshot()
    assert snapshot["step_wall_ms"]["count"] == 4
    assert snapshot["step_wall_ms"]["p50"] == pytest.approx(10.0)
    assert snapshot["samples_per_s"] == pytest.approx(3200.0)
    assert waiters() == []
    tel.close()


def test_a_full_queue_makes_the_dispatch_wait_and_drops_nothing(monkeypatch):
    monkeypatch.setattr(completions, "QUEUE_STEPS", 2)
    gate = threading.Event()
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: gate.wait() if x[0] is gate else real(x))
    done = completions.Completions(Telemetry().registry, lambda event: None)
    assert done.watch(0, time.perf_counter(), 32, gate) == 0.0
    while done._handed.qsize():  # until the waiter has taken it and blocks on it
        time.sleep(0.001)
    assert done.watch(1, time.perf_counter(), 32, gate) == 0.0
    assert done.watch(2, time.perf_counter(), 32, gate) == 0.0  # one taken, two queued
    threading.Timer(0.05, gate.set).start()
    assert done.watch(3, time.perf_counter(), 32, gate) > 0.02  # waited for a slot
    done.drain()
    assert done.completed == 4 and done.last_step == 3 and done.run_ahead == 0
    assert done.snapshot()["steps"] == 4
    done.close()
    assert waiters() == []


def test_every_step_handed_over_is_taken_in_once_and_in_order_under_a_short_switch_interval():
    """The waiter and the owning thread share a queue, a deque and two
    counters: two thousand steps through them with the interpreter switching
    threads every few microseconds, the owner absorbing as it goes."""
    import sys

    seen = []
    done = completions.Completions(Telemetry().registry, lambda event: None)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for k in range(2000):
            done.watch(k, time.perf_counter(), 32, np.float32(k), np.zeros((2, 3), np.float32) + k)
            if k % 7 == 0:
                done.absorb()
                seen += [row[0] for row in done.take_health(k)]
        done.drain()
        seen += [row[0] for row in done.take_health(1999)]
    finally:
        sys.setswitchinterval(interval)
    assert seen == list(range(2000))  # none lost, none twice, oldest first
    assert done.completed == done.dispatched == 2000 and done.run_ahead == 0
    snapshot = done.snapshot()
    assert snapshot["steps"] == 2000 and 0 <= snapshot["run_ahead_mean"] <= completions.QUEUE_STEPS
    done.close()
    assert waiters() == []


# -- C: health, a step late ----------------------------------------------------------------


def test_a_nonfinite_step_is_reported_once_with_its_own_step_though_seen_later(one_device, tmp_path):
    group = one_device
    jsonl = str(tmp_path / "metrics.jsonl")
    tel = Telemetry(metrics_jsonl=jsonl)
    monitor = HealthMonitor(telemetry=tel)
    ddp, state = make_ddp(group, loss=slow_loss, telemetry=tel, health_monitor=monitor)
    for k in range(6):
        state, _ = ddp.train_step(state, make_batch(seed=k, nan=(k == 3)))
        if k == 3:
            # the step is still on the device: nothing waited for it
            assert monitor.alerts == [] and ddp.host_overhead["health_wait"] == 0.0
    ddp.drain_steps()
    assert [(a["kind"], a["step"]) for a in monitor.alerts] == [("nonfinite", 3)]
    assert monitor.report()["observed_steps"] == 3  # 0, 1, 2: every later one is non-finite
    assert tel.registry.snapshot()["health_nonfinite_total"] >= 3
    snapshot = ddp.host_overhead_snapshot()
    assert snapshot["completions"]["health_lag_steps_max"] >= 1
    assert snapshot["health_wait_ms_per_step"] == 0.0
    ddp.drain_steps()  # again: nothing is observed twice
    assert len(monitor.alerts) == 1
    tel.close()
    assert validate_metrics_file(jsonl) == []
    ddp.shutdown()


def test_an_alert_of_the_last_step_of_fit_is_not_lost(one_device):
    group = one_device
    trainer = Trainer(slow_loss, optax.sgd(0.1), Algorithm.init("gradient_allreduce"),
                      process_group=group, watchdog_timeout_s=0,
                      telemetry=True, health_monitor=True)
    state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batches = [make_batch(seed=k, nan=(k == 4)) for k in range(5)]
    trainer.fit(state, batches, log_every=0)
    assert [(a["kind"], a["step"]) for a in trainer.health_monitor.alerts] == [("nonfinite", 4)]
    assert trainer.telemetry.snapshot()["completed_step"] == 4
    trainer.close()


def test_with_an_action_the_alert_is_raised_before_train_step_returns(one_device):
    group = one_device
    tel = Telemetry()
    monitor = HealthMonitor(telemetry=tel)
    seen = []
    monitor.register_action(lambda alert, state: seen.append((alert["step"], state)) or True)
    ddp, state = make_ddp(group, loss=slow_loss, telemetry=tel, health_monitor=monitor)
    for k in range(4):
        state, _ = ddp.train_step(state, make_batch(seed=k, nan=(k == 2)))
        assert [a["step"] for a in monitor.alerts] == ([2] if k >= 2 else [])
    # the action saw the state its alert is about
    assert [step for step, _ in seen] == [2] and seen[0][1] is not None
    snapshot = ddp.host_overhead_snapshot()
    assert snapshot["health_wait_ms_per_step"] > 5  # it waited for the device, and says so
    assert snapshot["completions"]["health_lag_steps_max"] == 0
    assert snapshot["completions"]["run_ahead_mean"] == 0
    tel.close()
    ddp.shutdown()


def test_a_monitor_without_a_hub_reads_before_the_next_step_and_counts_the_wait(one_device):
    group = one_device
    monitor = HealthMonitor()
    ddp, state = make_ddp(group, loss=slow_loss, health_monitor=monitor)
    for k in range(3):
        state, _ = ddp.train_step(state, make_batch(seed=k, nan=(k == 1)))
        assert [a["step"] for a in monitor.alerts] == ([1] if k >= 1 else [])
    assert waiters() == []
    assert ddp.host_overhead_snapshot()["health_wait_ms_per_step"] > 5
    ddp.shutdown()


# -- D: a stall has a cause --------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def time(self):
        return 1.7e9 + self.now


def test_an_interval_of_ten_medians_is_one_stall_event_that_names_its_phase(monkeypatch, tmp_path):
    clock = FakeClock()
    monkeypatch.setattr(completions, "time", clock)
    jsonl = str(tmp_path / "metrics.jsonl")
    tel = Telemetry(metrics_jsonl=jsonl)
    done = tel.completions

    def step(k, took, phases):
        """Step ``k`` completes ``took`` seconds after the one before, the
        fit thread passing through ``phases``: ``(name, seconds)``."""
        end = clock.now + took
        for name, seconds in phases:
            tel.enter_phase(name)
            clock.now += seconds
        assert clock.now <= end
        clock.now = end
        done._done.append((k, end - took, end, 32, None))
        done.absorb()

    usual = [("data", 0.004), ("dispatch", 0.003), ("wait", 0.003)]
    for k in range(12):
        step(k, 0.010, usual)
    assert done.snapshot()["stalls"] == 0 and len(done.stalls) == 0
    # the test sleeps in ``data`` (the feed, the runtime or the device is late)
    step(12, 0.100, [("data", 0.092), ("dispatch", 0.003), ("wait", 0.003)])
    for k in range(13, 20):
        step(k, 0.010, usual)
    snapshot = done.snapshot()
    assert snapshot["stalls"] == 1 and snapshot["stall_ms"] == pytest.approx(90.0)
    event, = done.stalls
    assert validate_metrics_event(event) == []
    assert event["step"] == 12 and event["interval_ms"] == pytest.approx(100.0)
    assert event["median_ms"] == pytest.approx(10.0) and event["excess_ms"] == pytest.approx(90.0)
    phases = event["phases_ms"]
    assert sum(phases.values()) == pytest.approx(event["interval_ms"])
    assert max(phases, key=phases.get) == "data" and phases["data"] == pytest.approx(92.0)
    metrics = tel.registry.snapshot()
    assert metrics["stalls_total"] == 1 and metrics["stall_ms_total"] == pytest.approx(90.0)
    # where there is a flight recorder, a dump carries the event beside the ring
    dump = tel.flight.dump(str(tmp_path / "flight_0.json"), reason="manual")
    assert validate_flight_dump(dump) == [] and dump["host_events"] == [event]
    assert dump["records"] == []  # and not in it: the ranks' rings are compared by number
    tel.close()
    assert validate_metrics_file(jsonl) == []
    with open(jsonl) as f:
        assert sum('"event": "stall"' in line for line in f) == 1


def test_the_collectors_pause_is_a_phase_and_what_precedes_the_ring_is_unknown():
    done = completions.Completions(Telemetry().registry, lambda event: None)
    done.watch(0, time.perf_counter(), 32, jnp.zeros(()))  # starts the waiter and the hook
    before = time.perf_counter()
    done.note_phase("dispatch")
    gc.collect()
    done.note_phase("wait")
    after = time.perf_counter()
    names = [name for name, _ in done._phases]
    names = names[names.index("dispatch"):]
    assert names[-1] == "wait"
    assert "gc" in names and names[names.index("gc") + 1] == "dispatch"  # back where it was
    spent = done.phases_between(before - 1.0, after)
    assert spent["unknown"] >= 1.0 and spent["gc"] > 0
    assert sum(spent.values()) == pytest.approx(after - before + 1.0)
    done.close()


# -- teardown ---------------------------------------------------------------------------------


def test_close_joins_the_waiter_and_removes_the_collectors_hook(group):
    hooks = list(gc.callbacks)
    tel = Telemetry()
    ddp, state = make_ddp(group, telemetry=tel)
    assert waiters() == [] and gc.callbacks == hooks  # nothing before the first step
    state, _ = ddp.train_step(state, make_batch())
    thread, = waiters()
    assert thread.daemon and len(gc.callbacks) == len(hooks) + 1
    tel.close()
    assert not thread.is_alive() and waiters() == [] and gc.callbacks == hooks
    assert tel.snapshot()["completed_step"] == 0  # it drained before it joined
    tel.close()  # idempotent
    ddp.shutdown()


def test_a_hub_nobody_closed_takes_its_waiter_and_hook_with_it(group):
    hooks = list(gc.callbacks)
    ddp, state = make_ddp(group, telemetry=Telemetry())
    state, losses = ddp.train_step(state, make_batch())
    jax.block_until_ready(losses)
    thread, = waiters()
    ddp.shutdown()
    del ddp, state, losses
    Telemetry(flight=None).close()  # the process's retry observer was the hub's: displace it
    for _ in range(3):
        gc.collect()
    thread.join(timeout=5)
    assert not thread.is_alive()
    gc.collect()  # the hook finds its hub gone and takes itself off
    assert gc.callbacks == hooks
