"""Set-up from inside the program: the process's record of cold events
(``observability/cold_start.py``), the spans that fill it, the compilations
``jax.monitoring`` reports charged to the span open on their thread, the
partition of what ended before ``since``, and the operator's one line.

On a record worked out on paper, and on a toy ``Trainer`` through
``init_state`` and two ``fit`` calls on two CPU devices."""

import collections
import inspect
import logging
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import Telemetry, cold_start
from bagua_tpu.observability.cold_start import (
    BACKEND_COMPILE_EVENT,
    CACHE_HIT_EVENT,
    CACHE_MISS_EVENT,
    CACHE_RETRIEVAL_EVENT,
    LOWERING_EVENT,
    TRACE_EVENT,
    ColdEvent,
    cold_host_span,
)
from bagua_tpu.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = [12, 16, 4]
CLASSES = ("import", "init", "step_trace", "step_compile", "step_text", "other_programs")
GROUP, TRAINER, INIT_STATE = cold_start.INIT_SPANS
BUILD, DISPATCH, TEXT = cold_start.STEP_SPANS


def fresh_record(patch):
    record = collections.deque(maxlen=cold_start.COLD_EVENTS_KEPT)
    patch.setattr(cold_start, "_record", record)
    return record


@pytest.fixture()
def record(monkeypatch):
    """A record of this test's own: the process's holds whatever the tests
    before it compiled."""
    return fresh_record(monkeypatch)


def batches(n, rows=16):
    rng = np.random.RandomState(0)
    return [(rng.randn(rows, LAYERS[0]).astype(np.float32),
             rng.randn(rows, LAYERS[-1]).astype(np.float32)) for _ in range(n)]


def named(events, name, detail=...):
    return [e for e in events if e.name == name and (detail is ... or e.detail == detail)]


def charged_to(events, span, detail=None):
    return [e for e in events if e.under == (span, detail)]


# -- a record worked out on paper ----------------------------------------------

def paper_record():
    """A start of 40 s.  The two imports [0, 2] and
    [2, 3] with a program of 0.5 s compiled inside the first; a trainer
    [3, 5] that builds its own group [3, 4] and compiles 0.25 s inside;
    ``init_state`` [5, 6]; a maker of the caller's [6, 10] (trace 1, lowering
    1, compile 2); the build [10, 19] with the text [11, 19] inside, which
    holds the step's trace 4, lowering 1 and compile 2.5 (a cache load of 2,
    kept on the record and added to nothing); the first
    dispatch [19, 20] that finds all of it made; a second maker on another
    thread while the build runs (charged to nothing); a window from 30 on with a retrace inside."""
    e = ColdEvent
    text, build, dispatch = (TEXT, "default"), (BUILD, "default"), (DISPATCH, "default")
    return [
        e(TRACE_EVENT, 0.5, 0.6, "eager_op", (cold_start.IMPORT_SPAN, "bagua_tpu")),
        e(LOWERING_EVENT, 0.6, 0.7, "jit(eager_op)", (cold_start.IMPORT_SPAN, "bagua_tpu")),
        e(BACKEND_COMPILE_EVENT, 0.7, 1.0, "jit(eager_op)", (cold_start.IMPORT_SPAN, "bagua_tpu")),
        e(cold_start.IMPORT_SPAN, 0.0, 2.0, "bagua_tpu", None),
        e(cold_start.IMPORT_SPAN, 2.0, 3.0, "bagua_tpu.trainer", None),
        e(BACKEND_COMPILE_EVENT, 3.5, 3.75, "jit(iota)", (GROUP, None)),
        e(GROUP, 3.0, 4.0, None, (TRAINER, None)),
        e(TRAINER, 3.0, 5.0, None, None),
        e(INIT_STATE, 5.0, 6.0, None, None),
        e(TRACE_EVENT, 6.0, 7.0, "<lambda>", None),
        e(LOWERING_EVENT, 7.0, 8.0, "jit(<lambda>)", None),
        e(CACHE_MISS_EVENT, 9.5, 9.5, None, None),
        e(BACKEND_COMPILE_EVENT, 8.0, 10.0, "jit(<lambda>)", None),
        e(TRACE_EVENT, 11.0, 15.0, "local_step", text),
        e(LOWERING_EVENT, 15.0, 16.0, "jit(local_step)", text),
        e(CACHE_HIT_EVENT, 18.0, 18.0, None, text),
        e(CACHE_RETRIEVAL_EVENT, 16.0, 18.0, None, text),
        e(BACKEND_COMPILE_EVENT, 16.0, 18.5, "jit(local_step)", text),
        e(TEXT, 11.0, 19.0, "default", build),
        e(BUILD, 10.0, 19.0, "default", None),
        e(BACKEND_COMPILE_EVENT, 12.0, 15.0, "jit(draw)", None),
        e(TRACE_EVENT, 19.5, 19.5625, "local_step", dispatch),
        e(DISPATCH, 19.0, 20.0, "default", None),
        e(TRACE_EVENT, 31.0, 32.0, "local_step", None),
        e(BACKEND_COMPILE_EVENT, 32.0, 35.0, "jit(local_step)", None),
    ]


PAPER = {
    "import": 2.0 + 1.0 - 0.5,
    "init": 2.0 + 1.0 - 0.25,        # the group inside the trainer counts once
    "step_trace": 4.0 + 1.0 + 0.0625,
    "step_compile": 2.5,
    "step_text": 8.0 - 7.5,
    "other_programs": 0.5 + 0.25 + 4.0 + 3.0,
    "other_programs_count": 4,
    "cache_hits": 1,
    "cache_misses": 1,
    "wall": 30.0,
}


@pytest.mark.parametrize("key", sorted(PAPER))
def test_partition_of_a_start_worked_out_on_paper(key, record):
    record.extend(paper_record())
    assert cold_start.setup_snapshot(until=30.0)[key] == pytest.approx(PAPER[key])


def test_the_classes_share_no_instant_and_the_longest_programs_are_named(record):
    record.extend(paper_record())
    found = cold_start.setup_snapshot(until=30.0)
    assert sum(found[c] for c in CLASSES) <= found["wall"]
    assert set(found) == set(CLASSES) | {
        "other_programs_count", "other_programs_longest", "cache_hits", "cache_misses", "wall"}
    # by function: a trace carries the function's name, the other two the module's
    assert found["other_programs_longest"] == [
        ("<lambda>", 4.0, 1), ("draw", 3.0, 1), ("eager_op", 0.5, 1), ("iota", 0.25, 1)]
    # the retrace inside the window is in the record, named, and in no class before it
    after = cold_start.setup_snapshot(until=40.0)
    assert after["other_programs"] == pytest.approx(found["other_programs"] + 4.0)
    assert ("local_step", 4.0, 1) in after["other_programs_longest"][:2]
    # a cut in the middle of the start: only what had ended
    early = cold_start.setup_snapshot(until=5.5)
    assert early["init"] == pytest.approx(1.75) and early["step_trace"] == 0


def test_what_the_variant_cost_to_make_is_the_programs_under_its_spans(record):
    record.extend(paper_record())
    assert cold_start.step_compile_seconds("default", since=10.0) == pytest.approx(7.5625)
    assert cold_start.step_compile_seconds("default", since=19.0) == pytest.approx(0.0625)
    assert cold_start.step_compile_seconds("another", since=0.0) == 0


def test_the_one_line_names_every_class_and_the_rest():
    line = cold_start.format_setup({
        "import": 1.4, "init": 2.0, "step_trace": 12.8, "step_compile": 1.9, "step_text": 0.0,
        "other_programs": 6.3, "other_programs_count": 41,
        "other_programs_longest": [("<lambda>", 4.5, 5), ("true_divide", 0.4, 12)],
        "cache_hits": 42, "cache_misses": 0, "wall": 31.2})
    assert line == ("set-up 31.2 s: import 1.4, init 2.0, step trace 12.8, step compile 1.9, "
                    "41 other programs 6.3 (<lambda> 4.5, true_divide 0.4), "
                    "cache 42 hits 0 misses, not named 6.8")
    assert "step text 0.5" in cold_start.format_setup(dict(
        PAPER, step_text=0.5, other_programs_longest=[]))


# -- spans and the listener ----------------------------------------------------


def test_a_cold_span_is_a_timed_span_that_lands_on_the_record(record):
    totals = {"build": 1.0}
    with cold_host_span("step", "build", totals, detail="default") as outer:
        with cold_host_span("step", "text", detail="default") as inner:  # no counter of its own
            pass
    assert totals["build"] == pytest.approx(1.0 + outer.elapsed) and inner.elapsed <= outer.elapsed
    first, second = list(record)  # appended as each closes
    assert (first.name, first.detail, first.under) == (TEXT, "default", (BUILD, "default"))
    assert (second.name, second.start, second.end, second.under) == (
        BUILD, outer.began, outer.began + outer.elapsed, None)
    # counted from an earlier reading, as the package's import is
    with cold_host_span("setup", "import", detail="pkg", began=outer.began) as late:
        pass
    assert record[-1].start == outer.began and late.elapsed >= outer.elapsed


def test_a_span_that_raises_is_recorded_and_closes(record):
    with pytest.raises(ZeroDivisionError):
        with cold_host_span("setup", "trainer"):
            1 / 0
    assert [e.name for e in record] == [TRAINER]
    jax.jit(lambda x: x - 5)(jnp.ones(3))
    assert all(e.under is None for e in record if e.name == BACKEND_COMPILE_EVENT)


def test_the_decorator_keeps_the_functions_face():
    assert bagua_tpu.init_process_group.__name__ == "init_process_group"
    assert "mesh_spec" in inspect.signature(bagua_tpu.init_process_group).parameters
    assert "profile_dir" in inspect.signature(Trainer.__init__).parameters
    assert Trainer.init_state.__doc__ == Trainer.init_state.__wrapped__.__doc__


def test_a_program_under_no_span_is_the_callers_and_named(record):
    def lonely(x):
        return x * 3 + 1

    jax.jit(lonely)(np.ones(7, np.float32))
    mine = [e for e in record if e.detail in ("lonely", "jit(lonely)")]
    assert {e.name for e in mine} == {TRACE_EVENT, LOWERING_EVENT, BACKEND_COMPILE_EVENT}
    assert all(e.under is None and e.end > e.start for e in mine)
    found = cold_start.setup_snapshot()
    assert found["other_programs_count"] == 1 and found["step_trace"] == found["step_compile"] == 0
    assert found["other_programs_longest"][0][0] == "lonely"
    assert found["other_programs"] == pytest.approx(sum(e.end - e.start for e in mine))


def test_a_program_is_charged_to_the_span_open_on_its_own_thread(record):
    def elsewhere(x):
        return x * 5 - 2

    def here(x):
        return x * 7 - 3

    worker = threading.Thread(target=lambda: jax.jit(elsewhere)(np.ones(5, np.float32)))
    with cold_host_span("setup", "init_state"):
        worker.start()
        worker.join()
        jax.jit(here)(np.ones(5, np.float32))
    theirs = named(record, BACKEND_COMPILE_EVENT, "jit(elsewhere)")
    mine = named(record, BACKEND_COMPILE_EVENT, "jit(here)")
    assert len(theirs) == len(mine) == 1
    assert theirs[0].under is None
    assert mine[0].under == (INIT_STATE, None)
    found = cold_start.setup_snapshot()
    # ``init`` is the span less its own thread's programs, and both programs are "other"
    span = named(record, INIT_STATE)[0]
    inside = sum(e.end - e.start for e in cold_start._programs(record) if e.under)
    assert found["init"] == pytest.approx(span.end - span.start - inside)
    assert found["other_programs_count"] == 2


def test_the_record_is_bounded_and_keeps_the_newest(record):
    assert record.maxlen == cold_start.COLD_EVENTS_KEPT == cold_start._record.maxlen
    for k in range(cold_start.COLD_EVENTS_KEPT + 10):
        with cold_host_span("step", "build", detail=str(k)):
            pass
    assert len(cold_start.cold_events()) == cold_start.COLD_EVENTS_KEPT
    assert cold_start.cold_events()[-1].detail == str(cold_start.COLD_EVENTS_KEPT + 9)
    assert cold_start.cold_events()[0].detail == "10"


def test_a_fresh_process_records_both_imports_once_and_apart():
    code = (
        "import time, jax\n"
        "t0 = time.perf_counter()\n"
        "import bagua_tpu.trainer, bagua_tpu\n"
        "t1 = time.perf_counter()\n"
        "from bagua_tpu.observability import cold_start as c\n"
        "ev = [e for e in c.cold_events() if e.name == c.IMPORT_SPAN]\n"
        "assert [e.detail for e in ev] == ['bagua_tpu', 'bagua_tpu.trainer'], ev\n"
        "assert t0 <= ev[0].start < ev[0].end <= ev[1].start < ev[1].end <= t1, ev\n"
        "assert all(e.under is None for e in ev)\n"
        "s = c.setup_snapshot()\n"
        "assert 0 < s['import'] <= t1 - t0 and s['import'] > 0.8 * (t1 - t0), (s, t1 - t0)\n"
        "assert s['init'] == s['step_trace'] == 0\n"
        "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd=REPO,
                          timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr[-3000:]


# -- a toy Trainer through its start -------------------------------------------


@pytest.fixture(scope="module")
def started():
    """``init_process_group``, ``Trainer``, ``init_state`` and two ``fit``
    calls of two steps, on a record of its own; the hub is there for the
    compile's wall."""
    with pytest.MonkeyPatch.context() as patch:
        fresh_record(patch)
        logged = []
        handler = logging.Handler(level=logging.INFO)
        handler.emit = lambda rec: logged.append(rec.getMessage())
        trainer_log = logging.getLogger("bagua_tpu.trainer")
        level = trainer_log.level
        trainer_log.addHandler(handler)
        trainer_log.setLevel(logging.INFO)
        begun = time.perf_counter()
        group = bagua_tpu.init_process_group(devices=jax.devices()[:2])
        trainer = Trainer(mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                          process_group=group, watchdog_timeout_s=0, telemetry=Telemetry())
        state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
        state = trainer.fit(state, batches(2), log_every=0)
        report = trainer.startup_report()
        state = trainer.fit(state, batches(2), log_every=0)
        box = {"trainer": trainer, "state": state, "begun": begun, "report": report,
               "logged": logged, "events": cold_start.cold_events()}
        yield box
        trainer_log.removeHandler(handler)
        trainer_log.setLevel(level)
        trainer.close()


def test_a_start_leaves_its_cold_spans_in_order(started):
    spans = [e for e in started["events"] if e.name.startswith("bagua_host/")]
    assert [(e.name, e.detail) for e in spans] == [
        (GROUP, None), (TRAINER, None), (INIT_STATE, None),
        (BUILD, "default"), (DISPATCH, "default")]
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
    assert spans[0].start >= started["begun"] and all(e.under is None for e in spans)


@pytest.mark.parametrize("event", [TRACE_EVENT, LOWERING_EVENT, BACKEND_COMPILE_EVENT])
def test_the_cold_build_holds_the_steps_trace_lowering_and_compile(started, event):
    build = named(started["events"], BUILD, "default")[0]
    inside = [e for e in charged_to(started["events"], BUILD, "default")
              if e.name in cold_start.PROGRAM_EVENTS]
    mine = [e for e in inside if e.name == event]
    assert mine and sum(e.end - e.start for e in mine) > 0
    assert all("local_step" in e.detail for e in mine)
    # the three kinds follow one another inside the build's interval
    assert sum(e.end - e.start for e in inside) <= build.end - build.start
    assert all(build.start <= e.start and e.end <= build.end for e in inside)
    # every jit inside the step is traced inside the step's trace, and is the step's
    assert len([e for e in inside if e.name == TRACE_EVENT]) == 1
    # ... and the dispatch that follows runs what the build compiled
    assert not [e for e in charged_to(started["events"], DISPATCH, "default")
                if e.name in cold_start.PROGRAM_EVENTS]


def test_only_the_outermost_stretch_of_a_thread_goes_on_the_record(record):
    def inner(x):
        return x * 2

    def outer(x):
        return jax.jit(inner)(x) + jnp.sin(x) + jax.jit(inner)(x + 1)

    jax.jit(outer)(np.ones(4, np.float32))
    assert [(e.name, e.detail) for e in record if e.name in cold_start.PROGRAM_EVENTS] == [
        (TRACE_EVENT, "outer"), (LOWERING_EVENT, "jit(outer)"), (BACKEND_COMPILE_EVENT, "jit(outer)")]
    assert cold_start._open.depth == 0
    # a trace that raises closes its stretch too
    with pytest.raises(ZeroDivisionError):
        jax.jit(lambda x: x / (1 // 0))(np.ones(2, np.float32))
    assert cold_start._open.depth == 0 and record[-1].name == TRACE_EVENT


def test_the_hub_takes_the_measured_compile_and_not_the_builds_wall(started):
    build = named(started["events"], BUILD, "default")[0]
    report = started["trainer"].telemetry.recompile.report()
    measured = 1e3 * cold_start.step_compile_seconds("default", since=started["begun"])
    assert report["compile_ms_by_variant"] == {"default": pytest.approx(measured, abs=1e-3)}
    assert 0 < report["compile_ms_total"] < 1e3 * (build.end - build.start)


def test_fit_logs_the_partition_once_and_the_report_stays(started):
    lines = [line for line in started["logged"] if line.startswith("set-up ")]
    assert len(lines) == 1 and "step trace" in lines[0] and "other programs" in lines[0]
    assert lines[0] == cold_start.format_setup(started["report"])
    report = started["report"]
    assert report["step_trace"] > 0 and report["step_compile"] > 0 and report["init"] > 0
    assert report["step_text"] == 0  # no profile_dir: no text
    assert sum(report[c] for c in CLASSES) <= report["wall"]
    jax.jit(lambda x: x * 11)(jnp.ones(2))  # a later compile is not the start's
    assert started["trainer"].startup_report() == report


def test_reset_clears_the_counters_sets_since_and_leaves_the_record(started):
    ddp = started["trainer"].ddp
    assert ddp.host_overhead_snapshot()["since"] is None
    before, t0 = cold_start.cold_events(), time.perf_counter()
    assert ddp.host_overhead_snapshot(reset=True)["steps"] == 4
    t1 = time.perf_counter()
    after = ddp.host_overhead_snapshot()
    assert t0 <= after["since"] <= t1
    assert after["steps"] == 1 and after["dispatch_ms_per_step"] == after["build_ms_per_step"] == 0
    assert cold_start.cold_events() == before
    # the classes of what ended before ``since`` share no instant
    found = cold_start.setup_snapshot(until=after["since"])
    first = min(e.start for e in before)
    assert all(found[c] >= 0 for c in CLASSES)
    assert sum(found[c] for c in CLASSES) <= after["since"] - first == pytest.approx(found["wall"])


def test_a_step_whose_variant_is_built_adds_no_event_and_opens_no_cold_span(started, monkeypatch):
    def never(self):
        raise AssertionError("a cold span on a built variant")

    monkeypatch.setattr(cold_host_span, "__enter__", never)
    trainer, before = started["trainer"], cold_start.cold_events()
    started["state"] = trainer.fit(started["state"], batches(3), log_every=0)
    assert cold_start.cold_events() == before


@pytest.mark.parametrize("how", ["a second variant", "dropped variants"])
def test_a_build_inside_the_window_is_named_with_its_variant(started, how, monkeypatch):
    trainer = started["trainer"]
    ddp = trainer.ddp
    ddp.host_overhead_snapshot(reset=True)
    since = ddp.host_overhead_snapshot()["since"]
    before = cold_start.setup_snapshot(until=since)
    if how == "a second variant":
        monkeypatch.setattr(ddp.impl, "step_variant", lambda step: "second")
    else:
        ddp.drop_step_variants()
    variant = "second" if how == "a second variant" else "default"
    started["state"] = trainer.fit(started["state"], batches(2), log_every=0)
    late = [e for e in cold_start.cold_events() if e.start >= since]
    assert [(e.name, e.detail) for e in late if e.name.startswith("bagua_host/")] == [
        (BUILD, variant), (DISPATCH, variant)]
    compiled = [e for e in late if e.name == BACKEND_COMPILE_EVENT]
    assert [(e.detail, e.under) for e in compiled] == [("jit(local_step)", (BUILD, variant))]
    assert compiled[0].end - compiled[0].start > 0
    # ... and in no class of the set-up that ended at ``since``
    assert cold_start.setup_snapshot(until=since) == before
    assert ddp.host_overhead_snapshot()["build_ms_per_step"] > 0


def test_a_profiling_trainer_reads_the_text_of_the_step_the_build_made(record, tmp_path):
    """The step's text is the text of the executable the build compiled and
    the dispatch runs: nothing is traced, lowered or compiled for it."""
    metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    group = bagua_tpu.init_process_group(devices=jax.devices()[:2])
    trainer = Trainer(mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(), process_group=group,
                      watchdog_timeout_s=0, profile_dir=str(tmp_path), profile_steps=(50, 51))
    try:
        state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
        trainer.fit(state, batches(1), log_every=0)
    finally:
        trainer.close()
        jax.config.update("jax_compilation_cache_include_metadata_in_key", metadata)
    text = named(record, TEXT, "default")
    assert len(text) == 1 and text[0].under == (BUILD, "default")
    assert not [e for e in charged_to(record, TEXT, "default") if e.name in cold_start.PROGRAM_EVENTS]
    assert [e.name for e in charged_to(record, BUILD, "default")
            if e.name in cold_start.PROGRAM_EVENTS] == [
        TRACE_EVENT, LOWERING_EVENT, BACKEND_COMPILE_EVENT]
    assert trainer.ddp.step_text() == trainer.ddp.compiled_step().as_text()
    found = trainer.startup_report()
    assert found["step_trace"] > 0 and found["step_compile"] > 0
    assert found["step_text"] == pytest.approx(text[0].end - text[0].start)
