"""The process's record of cold events: what happens once or rarely before
(and now and then between) the steps that ``ddp.host_overhead`` counts.

A cold event is an import, building the group, building the ``Trainer``,
``init_state``, building a step variant, that variant's first dispatch, or a
compilation.  The first six are :class:`cold_host_span`\\ s: the
``timed_host_span`` of the per-step counters (one ``bagua_host/…`` profiler
annotation and one ``perf_counter`` interval, one measurement) that is also
appended to the record as ``(name, start, end, detail)``.  A compilation is
what ``jax.monitoring`` reports (tracing a function to a jaxpr, lowering it,
the backend's compile or the persistent cache's answer), taken by the one
listener this module registers when it is imported (the outermost stretch
of a thread only: a ``jit`` traced inside the step's trace is the step's);
each is charged to the innermost cold span open *on its thread*, so an event
is the step's because the step's span was open and not because of the
function's name, and one under no span of the program is the caller's own
program (``under`` None: "outside").

The record is the process's, bounded (the oldest events go first), and
nothing resets it: ``host_overhead_snapshot(reset=True)`` says since when the
per-step counters count (``since``), and :func:`setup_snapshot` partitions
what ended before an instant.
"""

import collections
import functools
import re
import threading
import time
from typing import List, NamedTuple, Optional, Tuple

import jax

from bagua_tpu.observability.annotations import timed_host_span
from bagua_tpu.observability.scope_grammar import format_host_span

__all__ = [
    "ColdEvent",
    "cold_host_span",
    "cold_event",
    "cold_events",
    "setup_snapshot",
    "step_compile_seconds",
    "format_setup",
]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"  # a cache load too
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: the durations that are a program's way from a function to an executable.
#: The cache's retrieval time lies inside the backend compile's and is kept
#: for the reader of the record, never added to a class
PROGRAM_EVENTS = (TRACE_EVENT, LOWERING_EVENT, BACKEND_COMPILE_EVENT)

IMPORT_SPAN = format_host_span("setup/import")
#: ``init_process_group``, ``Trainer.__init__``, ``Trainer.init_state``
INIT_SPANS = tuple(format_host_span(f"setup/{key}") for key in ("group", "trainer", "init_state"))
#: what a ``Trainer`` with a ``profile_dir`` does inside the build for the
#: step's text: ``as_text()`` of the executable the build compiled (since PR
#: 47; it lowered and compiled the step itself before, and a record that
#: holds programs under this span still has them counted as the step's)
TEXT_SPAN = format_host_span("step/text")
#: a missed step variant: building it, which traces, lowers and compiles it,
#: and the dispatch after it, whose one cold event of its own is the state's
#: move into the layouts the step takes it in (``bagua_host/step/layout``,
#: ``detail``: the variant, the leaves moved and their bytes a device)
STEP_SPANS = tuple(format_host_span(f"step/{key}") for key in ("build", "dispatch")) + (TEXT_SPAN,)

#: the classes of :func:`setup_snapshot`, which share no instant
CLASSES = ("import", "init", "step_trace", "step_compile", "step_text", "other_programs")

#: events the record keeps; a start makes about a hundred
COLD_EVENTS_KEPT = 4096


class ColdEvent(NamedTuple):
    """One line of the record, times on ``time.perf_counter``."""

    name: str  # a span's ``bagua_host/...`` name, or the ``jax.monitoring`` event's
    start: float
    end: float
    #: of a span what its opener gave (a step variant's name, a module's); of
    #: a compilation the ``fun_name`` JAX sent with it
    detail: Optional[str]
    #: ``(name, detail)`` of the innermost cold span open on the thread, or None
    under: Optional[Tuple[str, Optional[str]]]


_record = collections.deque(maxlen=COLD_EVENTS_KEPT)
#: ``.span``: the innermost cold span open on this thread; ``.depth``: how
#: many of JAX's trace, lowering and compile stretches are open on it
_open = threading.local()


def _under():
    span = getattr(_open, "span", None)
    return None if span is None else (span.name, span.detail)


class cold_host_span(timed_host_span):
    """``timed_host_span`` around a cold event: while it is open the
    compilations of its thread are charged to it, and when it closes it is
    appended to the record.  ``totals`` may be None (a span with no per-step
    counter); ``began`` is an earlier ``perf_counter`` reading to count from,
    for the import span, whose first stretch is importing this module."""

    __slots__ = ("name", "detail", "_outer", "_from")

    def __init__(self, where: str, key: str, totals: Optional[dict] = None,
                 detail: Optional[str] = None, began: Optional[float] = None):
        super().__init__(where, key, totals)
        self.name = format_host_span(f"{where}/{key}")
        self.detail, self._from = detail, began

    def __enter__(self):
        self._outer = getattr(_open, "span", None)
        _open.span = self
        super().__enter__()
        if self._from is not None:
            self.began = self._from
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.elapsed = end - self.began
        if self._totals is not None:
            self._totals[self._key] += self.elapsed
        self._span.__exit__(*exc)
        _open.span = self._outer
        _record.append(ColdEvent(self.name, self.began, end, self.detail, _under()))
        return False


def cold_event(where: str, key: str):
    """Decorator: every call of the function is the cold event
    ``bagua_host/<where>/<key>``."""
    def decorate(fn):
        @functools.wraps(fn)
        def cold(*args, **kwargs):
            with cold_host_span(where, key):
                return fn(*args, **kwargs)

        return cold

    return decorate


def _on_start(event: str, *_, **__) -> None:
    # JAX sends a scalar (the start's wall time) as each stretch begins
    if event in PROGRAM_EVENTS:
        _open.depth = getattr(_open, "depth", 0) + 1


def _on_duration(event: str, duration: float, fun_name: Optional[str] = None, **_) -> None:
    # ... and this as the stretch ends, so now is its end.  A function traced
    # while another is traced (every ``jit`` inside the step: thousands in one
    # start) reports a stretch of its own inside the outer one's: only the
    # outermost of a thread goes on the record, and holds the others' time
    if event in PROGRAM_EVENTS:
        _open.depth = depth = max(getattr(_open, "depth", 0) - 1, 0)
        if depth:
            return
    elif event != CACHE_RETRIEVAL_EVENT:
        return
    end = time.perf_counter()
    _record.append(ColdEvent(event, end - duration, end, fun_name, _under()))


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT or event == CACHE_MISS_EVENT:
        now = time.perf_counter()
        _record.append(ColdEvent(event, now, now, None, _under()))


# once per process: a listener cannot be taken back, and it runs only when
# JAX compiles
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def cold_events() -> List[ColdEvent]:
    """The record, oldest first: copied in one call, so that a thread that
    compiles meanwhile does not change it under the reader."""
    return list(_record)


def _programs(events) -> List[ColdEvent]:
    """The trace, lowering and backend-compile events of ``events``."""
    return [e for e in events if e.name in PROGRAM_EVENTS]


def _program_name(fun_name: Optional[str]) -> str:
    """One name for a function's three events: the trace carries the
    function's name (``local_step``), the other two the module's
    (``jit(local_step)``)."""
    wrapped = re.fullmatch(r"(?:jit|pmap)\((.*)\)", fun_name or "?")
    return wrapped.group(1) if wrapped else fun_name or "?"


def setup_snapshot(until: Optional[float] = None) -> dict:
    """Seconds of the record's events that ended before ``until`` (a
    ``perf_counter`` instant, as ``host_overhead_snapshot()["since"]``; None:
    now), in classes that share no instant:

    * ``import``: the module bodies of ``bagua_tpu`` and ``bagua_tpu.trainer``;
    * ``init``: ``init_process_group``, ``Trainer.__init__`` and
      ``init_state``; of both, less the programs compiled inside them;
    * ``step_trace`` / ``step_compile``: tracing and lowering / the backend's
      compile or cache load, inside the build or the first dispatch of a step
      variant that was missed;
    * ``step_text``: what printing the step's text (traced runs only) took,
      less any program made inside it;
    * ``other_programs``: tracing, lowering and backend compile of every
      other program, with ``other_programs_count`` (backend compiles) and
      ``other_programs_longest``, the five largest ``(name, seconds,
      backend compiles)`` by function.

    ``cache_hits`` and ``cache_misses`` count the persistent cache's answers,
    ``wall`` is the time from the record's first event to ``until``.  What no
    class holds (the interpreter, ``import jax``, the runtime's start, the
    caller's own host and device work) is the caller's to report as the
    remainder."""
    if until is None:
        until = time.perf_counter()
    events = [e for e in cold_events() if e.end <= until]
    classes = dict.fromkeys(CLASSES, 0.0)
    others = collections.defaultdict(lambda: [0.0, 0])
    for e in events:
        if e.name == IMPORT_SPAN:
            classes["import"] += e.end - e.start
        elif e.name in INIT_SPANS and (e.under is None or e.under[0] not in INIT_SPANS):
            # the group that ``Trainer.__init__`` builds by default is the trainer's
            classes["init"] += e.end - e.start
        elif e.name == TEXT_SPAN:
            classes["step_text"] += e.end - e.start
    for e in _programs(events):
        seconds, span = e.end - e.start, e.under[0] if e.under else None
        if span in STEP_SPANS:
            classes["step_compile" if e.name == BACKEND_COMPILE_EVENT else "step_trace"] += seconds
            if span == TEXT_SPAN:
                classes["step_text"] -= seconds
            continue
        if span == IMPORT_SPAN:
            classes["import"] -= seconds
        elif span in INIT_SPANS:
            classes["init"] -= seconds
        classes["other_programs"] += seconds
        entry = others[_program_name(e.detail)]
        entry[0] += seconds
        entry[1] += e.name == BACKEND_COMPILE_EVENT
    return {
        **classes,
        "other_programs_count": sum(n for _, n in others.values()),
        "other_programs_longest": [
            (name, seconds, n) for name, (seconds, n) in
            sorted(others.items(), key=lambda item: -item[1][0])[:5]],
        "cache_hits": sum(e.name == CACHE_HIT_EVENT for e in events),
        "cache_misses": sum(e.name == CACHE_MISS_EVENT for e in events),
        "wall": until - min((e.start for e in events), default=until),
    }


def step_compile_seconds(variant: str, since: float) -> float:
    """Seconds of tracing, lowering and backend compile charged to the build
    and the first dispatch of ``variant`` from ``since`` on: what the step
    variant cost to make, without the dispatch's other work."""
    return sum(
        e.end - e.start
        for e in _programs(e for e in cold_events() if e.start >= since)
        if e.under is not None and e.under[0] in STEP_SPANS and e.under[1] == variant)


def format_setup(snapshot: dict) -> str:
    """One line for the log: ``set-up 31.2 s: import 1.4, init 2.0, ...``."""
    named = sum(snapshot[k] for k in CLASSES)
    text = f", step text {snapshot['step_text']:.1f}" if snapshot["step_text"] else ""
    longest = ", ".join(f"{name} {seconds:.1f}" for name, seconds, _ in
                        snapshot["other_programs_longest"][:3])
    return (
        f"set-up {snapshot['wall']:.1f} s: import {snapshot['import']:.1f}, "
        f"init {snapshot['init']:.1f}, step trace {snapshot['step_trace']:.1f}, "
        f"step compile {snapshot['step_compile']:.1f}{text}, "
        f"{snapshot['other_programs_count']} other programs "
        f"{snapshot['other_programs']:.1f}" + (f" ({longest})" if longest else "")
        + f", cache {snapshot['cache_hits']} hits {snapshot['cache_misses']} misses, "
        f"not named {snapshot['wall'] - named:.1f}")
