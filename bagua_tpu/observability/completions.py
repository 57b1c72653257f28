"""Steps seen to complete, from inside the program.

The engine's dispatch returns before the device has run the step, so a wall
taken around it is the wall of the *enqueue*.  With a
:class:`~bagua_tpu.observability.telemetry.Telemetry` hub attached the engine
hands each dispatched step's small results (the per-rank losses and, with a
health monitor, the ``(size, 3)`` health vector; never the state) to one
waiter thread, which blocks on them in order under the host span
``bagua_host/wait/step`` and stamps ``perf_counter`` as each becomes ready.
Everything else happens on the thread that owns the engine, when it next
asks (:meth:`Completions.absorb`): the completion intervals behind the hub's
``step_wall_ms`` and ``samples_per_s``, the run-ahead, the health rows a
:class:`~bagua_tpu.observability.health.HealthMonitor` observes a step late,
and the stalls.

A stall is a completion interval over :data:`STALL_FACTOR` times the median
of the last :data:`STALL_WINDOW`.  The hub logs the fit thread's phase
transitions (``data``, ``dispatch``, ``wait``, and the collector's pauses as
``gc``), so a stall is reported with the milliseconds of its interval that
the host spent in each phase: ``data`` is the feed, the runtime or the
device being late, ``dispatch`` the runtime's lock, ``gc`` the collector.
"""

import collections
import gc
import logging
import queue
import statistics
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bagua_tpu.observability.annotations import host_span

logger = logging.getLogger(__name__)

__all__ = ["Completions", "read_health"]

#: steps that may be handed over and not yet seen to complete: far above any
#: run-ahead, and what bounds it where nothing else does (the dispatch waits)
QUEUE_STEPS = 64
#: a completion interval over this many medians is a stall ...
STALL_FACTOR = 2.0
#: ... of this many intervals before it, once there are :data:`STALL_MIN`
STALL_WINDOW = 32
STALL_MIN = 8
#: phase transitions kept: a dozen steps' worth and their collector pauses
PHASE_RING = 128
#: completion intervals kept of one stretch between resets
INTERVAL_RING = 1 << 16
#: stall events kept for a dump
STALL_RING = 32


def read_health(arr) -> Tuple[float, float, int]:
    """The rank-stacked ``(size, 3)`` health vector on the host: mean loss,
    largest gradient norm, summed non-finite count.  On a multi-host group
    only this process' shards are addressable; every rank reaches the same
    alert decision from its own slice (all slices of a replicated reduction
    agree, and per-rank values differ only in the local loss and gradient
    terms the detector's thresholds are far above)."""
    if hasattr(arr, "is_fully_addressable") and not arr.is_fully_addressable:
        rows = np.concatenate(
            [np.asarray(s.data).reshape(-1, 3) for s in arr.addressable_shards])
    else:
        rows = np.asarray(arr).reshape(-1, 3)
    return float(np.mean(rows[:, 0])), float(np.max(rows[:, 1])), int(np.sum(rows[:, 2]))


class _Waiter(threading.Thread):
    """Blocks on the steps handed over, in order, and stamps each.  It holds
    the queue and the list it fills, not the hub: a hub nobody closed can be
    collected, and its finalizer ends the thread."""

    def __init__(self, handed: queue.Queue, done: collections.deque):
        super().__init__(name="bagua-step-waiter", daemon=True)
        self._handed, self._done = handed, done
        self.completed = 0
        self.last_step = -1

    def run(self) -> None:
        import jax

        while True:
            item = self._handed.get()
            try:
                if item is None:
                    return
                step, began, n_samples, losses, health = item
                rows = None
                try:
                    with host_span("wait/step"):
                        jax.block_until_ready((losses, health))
                    stamp = time.perf_counter()
                    if health is not None:
                        rows = read_health(health)
                except Exception:  # the step raised on the device: it is over all the same
                    stamp = time.perf_counter()
                    logger.exception("step %d raised on the device", step)
                del losses, health, item
                self._done.append((step, began, stamp, n_samples, rows))
                self.last_step = step
                self.completed += 1
            finally:
                self._handed.task_done()


def _gc_hook(ref):
    def hook(phase, info):
        completions = ref()
        if completions is None:  # its hub went without ``close()``
            if hook in gc.callbacks:
                gc.callbacks.remove(hook)
        elif phase == "start":
            completions._phases.append(("gc", time.perf_counter()))
        else:
            completions._phases.append((completions.phase, time.perf_counter()))

    return hook


class Completions:
    """The hub's record of steps completing.  ``watch`` and ``note_phase``
    are the engine's dispatch path; ``absorb``, ``take_health``, ``drain``
    and ``snapshot`` run on the same thread; only ``last_step`` and
    ``run_ahead`` are read from elsewhere (a watchdog's dump)."""

    def __init__(self, registry, on_stall: Callable[[Dict], None]):
        self.registry = registry
        self._on_stall = on_stall
        self._handed: queue.Queue = queue.Queue(maxsize=QUEUE_STEPS)
        self._done: collections.deque = collections.deque()
        self._waiter: Optional[_Waiter] = None
        self._hook = None
        self.dispatched = 0
        self.watched = None  # the last step handed over
        #: the fit thread's phase, the collector's pauses apart
        self.phase = "init"
        self._phases: collections.deque = collections.deque(maxlen=PHASE_RING)
        self._last_stamp: Optional[float] = None
        self._recent: collections.deque = collections.deque(maxlen=STALL_WINDOW)
        self._health: List[Tuple] = []
        self._instruments = None
        self.stalls: collections.deque = collections.deque(maxlen=STALL_RING)
        self._reset()

    def _reset(self) -> None:
        self._intervals: collections.deque = collections.deque(maxlen=INTERVAL_RING)
        self._run_ahead_sum = 0
        self._run_ahead_n = 0
        self._stalls = 0
        self._stall_s = 0.0
        self._health_lag_max = 0
        self._completed_at_reset = self.completed

    # -- the dispatch path ---------------------------------------------------

    @property
    def completed(self) -> int:
        return self._waiter.completed if self._waiter is not None else 0

    @property
    def last_step(self) -> int:
        """The last step seen to complete (-1 before any)."""
        return self._waiter.last_step if self._waiter is not None else -1

    @property
    def run_ahead(self) -> int:
        return self.dispatched - self.completed

    def note_phase(self, phase: str) -> None:
        self.phase = phase
        self._phases.append((phase, time.perf_counter()))

    def watch(self, step: int, began: float, n_samples: int, losses, health=None) -> float:
        """Hands a dispatched step's results to the waiter; ``began`` is the
        ``perf_counter`` reading its dispatch began at.  Returns the seconds
        the hand-over waited for a free slot (0.0 but when the dispatch is
        :data:`QUEUE_STEPS` ahead of the device)."""
        if self._waiter is None or not self._waiter.is_alive():
            self._start()
        self._run_ahead_sum += self.dispatched - self._waiter.completed
        self._run_ahead_n += 1
        self.dispatched += 1
        self.watched = step
        item = (step, began, n_samples, losses, health)
        try:
            self._handed.put_nowait(item)
            return 0.0
        except queue.Full:
            waited = time.perf_counter()
            with host_span("step/health_wait"):
                self._handed.put(item)
            return time.perf_counter() - waited

    def _start(self) -> None:
        """The waiter and the collector's hook, at the first step handed over
        (and again at the first after a ``close()``)."""
        before, self._waiter = self._waiter, _Waiter(self._handed, self._done)
        if before is None:
            weakref.finalize(self, self._handed.put, None)
        else:
            self._waiter.completed, self._waiter.last_step = before.completed, before.last_step
        self._waiter.start()
        self._hook = _gc_hook(weakref.ref(self))
        gc.callbacks.append(self._hook)

    # -- the owning thread's side -----------------------------------------------

    def absorb(self) -> None:
        """Takes in every completion stamped since the last call."""
        done = self._done
        while done:
            step, began, stamp, n_samples, rows = done.popleft()
            last, self._last_stamp = self._last_stamp, stamp
            # the first step of a stretch has no completion before it: its
            # interval runs from its own dispatch
            interval = stamp - (began if last is None else last)
            self._intervals.append(interval)
            steps, wall, rate = self._instruments or self._make_instruments()
            steps.inc()
            wall.observe(interval * 1e3)
            rate.set(round(n_samples / interval, 3) if interval > 0 else 0.0)
            recent = self._recent
            if last is not None and len(recent) >= STALL_MIN:
                median = statistics.median(recent)
                if interval > STALL_FACTOR * median:
                    self._stall(step, last, stamp, median)
            recent.append(interval)
            if rows is not None:
                self._health.append((step,) + rows)

    def _make_instruments(self):
        r = self.registry
        self._instruments = (
            r.counter("steps_completed_total", help="training steps seen to complete"),
            r.histogram("step_wall_ms", help="interval between steps completing"),
            r.gauge("samples_per_s", help="throughput over the last completion interval"),
        )
        return self._instruments

    def _stall(self, step: int, start: float, end: float, median: float) -> None:
        excess = (end - start) - median
        self._stalls += 1
        self._stall_s += excess
        r = self.registry
        r.counter("stalls_total", help="completion intervals over twice the median").inc()
        r.counter("stall_ms_total", help="their excess over the median").inc(excess * 1e3)
        event = {
            "event": "stall", "step": int(step), "ts": time.time(),
            "interval_ms": round((end - start) * 1e3, 3),
            "median_ms": round(median * 1e3, 3),
            "excess_ms": round(excess * 1e3, 3),
            "phases_ms": {k: round(v * 1e3, 3) for k, v in self.phases_between(start, end).items()},
        }
        self.stalls.append(event)
        try:
            self._on_stall(event)
        except Exception:
            logger.exception("stall event emission failed")

    def phases_between(self, start: float, end: float) -> Dict[str, float]:
        """Seconds of ``[start, end]`` the fit thread spent in each phase,
        by the transitions kept; what lies before the oldest is ``unknown``.
        The parts sum to the interval."""
        for _ in range(4):
            try:
                ring = list(self._phases)
                break
            except RuntimeError:  # the collector's hook appended meanwhile
                continue
        else:
            ring = []
        out: Dict[str, float] = {}
        at, phase = start, "unknown"
        for name, t in ring:
            if t >= end:
                break
            if t > at:
                out[phase] = out.get(phase, 0.0) + (t - at)
                at = t
            phase = name
        out[phase] = out.get(phase, 0.0) + (end - at)
        return out

    def take_health(self, step: int) -> List[Tuple]:
        """The health rows ``(step, loss, grad_norm, nonfinite)`` absorbed and
        not yet taken, oldest first; ``step`` is the one being dispatched,
        against which their lateness is counted."""
        rows, self._health = self._health, []
        if rows:
            self._health_lag_max = max(self._health_lag_max, step - rows[0][0])
        return rows

    def drain(self) -> None:
        """Returns when every step handed over has been stamped and absorbed.
        The next step begins a stretch of its own."""
        if self._waiter is not None:
            self._handed.join()
        self.absorb()
        self._last_stamp = None

    def snapshot(self, reset: bool = False) -> Dict:
        """The ``completions`` entry of ``ddp.host_overhead_snapshot()``."""
        self.absorb()
        intervals = np.asarray(self._intervals, dtype=np.float64) * 1e3
        out = {
            "steps": self.completed - self._completed_at_reset,
            "interval_ms": {
                "p50": float(np.quantile(intervals, 0.5)),
                "p95": float(np.quantile(intervals, 0.95)),
                "max": float(intervals.max()),
            } if len(intervals) else {},
            "run_ahead_mean": self._run_ahead_sum / max(1, self._run_ahead_n),
            "stalls": self._stalls,
            "stall_ms": self._stall_s * 1e3,
            "health_lag_steps_max": self._health_lag_max,
        }
        if reset:
            self._reset()
        return out

    def close(self) -> None:
        """Drains and joins the waiter and removes the collector's hook."""
        if self._hook is not None:
            if self._hook in gc.callbacks:
                gc.callbacks.remove(self._hook)
            self._hook = None
        if self._waiter is not None and self._waiter.is_alive():
            self._handed.join()
            self._handed.put(None)
            self._waiter.join()
        self.absorb()
