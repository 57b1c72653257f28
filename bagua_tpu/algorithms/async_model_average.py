"""Asynchronous model averaging.

TPU-native redesign of the reference's ``async_model_average.py`` +
``decentralized_full_precision_asynchronous.rs``.  The reference runs a
background thread that continuously allreduce-averages the live weights on a
dedicated CUDA stream while forward/backward proceeds, with weight locks and
a 1-byte MIN-allreduce abort negotiation
(``async_model_average.py:208-230``,
``decentralized_full_precision_asynchronous.rs:98-171``).  The defining
property: **training never blocks on the average**; staleness is tolerated.

Under XLA arrays are immutable and a step is a pure function, so "average the
live weights in place" does not map directly — but the property does:

* A daemon **averager thread** wakes every ``sync_interval_ms``, snapshots the
  current rank-stacked parameters (a Python ref — jax.Arrays are immutable, so
  the snapshot is free), and dispatches a separately-jitted **delta
  program** ``delta = group_mean - snapshot`` into fresh buffers.  The device
  executes it interleaved with training steps (the role of the reference's
  comm stream); the averager NEVER waits on the result — it publishes the
  in-flight delta and goes back to sleep.  (Returning the delta rather than
  ``(mean, snapshot_copy)`` halves the program's HBM writes and the fold's
  reads.)
* Right before a step dispatch the engine **folds** a published delta into the
  training state — ``params <- params + delta`` — but ONLY if its buffers
  have actually landed (``Array.is_ready()``, a non-blocking query).  An
  in-flight average is simply left pending for a later step, so the training
  loop never blocks on the averager, host- or device-side.  This is the
  well-defined functional analog of the reference's tolerated race between
  the averaging write-back and concurrent optimizer updates: progress made
  since the snapshot survives, staleness in the average is accepted.
* The steady-state train step itself contains **zero collectives** (warmup
  steps route through a ``lax.cond`` gradient allreduce, after which the
  branch is dead) — so step cadence is independent of averaging cadence.
* ``abort()`` mirrors the reference's negotiated abort: the averager
  contributes a 0 to a group MIN every cycle (``_negotiate``); averaging only
  runs when every rank contributes 1.  ``abort()`` waits for any in-flight
  average to drain, discards the undelivered result, and parks the thread;
  ``resume()`` re-arms it.  (Reference ``:232-305``.)

Dispatch-order safety: the engine serializes step dispatch with the averager's
snapshot+dispatch via ``host_dispatch_lock`` (microseconds — only the
*enqueue* is serialized, not device execution).  This is required because the
step donates its input buffers; sampling under the lock guarantees the
averager only ever reads the freshest, not-yet-donated parameters.
"""

import logging
import threading

import jax
import jax.numpy as jnp

from bagua_tpu.algorithms.base import Algorithm, AlgorithmImpl, StepContext
from bagua_tpu.communication import ALL_AXES, ReduceOp, allreduce_inplace
from jax.sharding import PartitionSpec as P


class AsyncModelAverageAlgorithmImpl(AlgorithmImpl):

    def __init__(
        self,
        process_group,
        peer_selection_mode: str = "all",
        sync_interval_ms: int = 500,
        warmup_steps: int = 0,
    ):
        super().__init__(process_group)
        if peer_selection_mode != "all":
            raise ValueError(
                "async model average supports peer_selection_mode='all' "
                "(the reference rejects others too, async_model_average.py:84-90)"
            )
        self.peer_selection_mode = peer_selection_mode
        self.sync_interval_ms = sync_interval_ms
        self.warmup_steps = warmup_steps

        self._status = "running"
        self._latest = None  # rank-stacked params of the newest dispatched step
        self._published_step = 0
        self._pending = None  # (generation, delta tree) awaiting fold
        # Set by the averager thread once the pending delta's buffers have
        # landed; read by host_pre_dispatch.  The step path reads this plain
        # bool and performs ZERO backend queries (a per-leaf ``is_ready()``
        # probe is one query per leaf per step); readiness detection lives
        # on the averager thread (``_watch_pending``).  Guarded by
        # _pending_lock.
        self._pending_ready = False
        # Double-fold guard.  A delta is ``mean(snap) - snap``; applying it is
        # only correct if no OTHER fold landed between its snapshot and its
        # consumption — an intervening fold's correction would be re-applied
        # (observed on the 8-dev CPU sim as the rank spread re-inverting to
        # its full initial magnitude at lr=0).  Optimizer progress in that
        # window is fine (the tolerated staleness); a second fold is not.
        # The counter increments on every fold; stale-generation deltas are
        # dropped.  Guarded by ``_pending_lock``.
        self._fold_generation = 0
        #: deltas dropped while possibly still in flight (stale generation /
        #: unusable) — drained by the next cycle or abort() so no untracked
        #: program outlives the averager's device-quiescence guarantees.
        #: Guarded by ``_pending_lock``.
        self._orphans = []
        self._pending_lock = threading.Lock()
        self._cycle_lock = threading.Lock()  # held across one averaging cycle
        self.host_dispatch_lock = threading.Lock()  # shared with the engine
        self._thread = None
        self._stop_event = threading.Event()  # per-thread; replaced on spawn
        self._wake = threading.Event()
        self._shutdown = False
        self._jit_average = None
        # The delta is consumed exactly once — donate its buffers to the fold.
        self._jit_fold = jax.jit(
            lambda params, delta: jax.tree.map(
                lambda p, d: p + d, params, delta
            ),
            donate_argnums=(1,),
        )
        self.folds_applied = 0  # observability: how many averages landed
        self.folds_failed = 0  # observability: how many folds were dropped

    # -- the average program -------------------------------------------------

    def _build_average(self):
        def local(p):
            def delta_of(x):
                # Uniform stacking: every device holds size/n_dev rows, so the
                # pmean of local means is the group mean.  Emitting the delta
                # (mean - snapshot) keeps the output a fresh buffer — no
                # aliasing with the live training params, which the next step
                # will donate — while halving the traffic of returning
                # (mean, snapshot_copy) pairs.
                m = jax.lax.pmean(jnp.mean(x, axis=0, keepdims=True), ALL_AXES)
                return jnp.broadcast_to(m, x.shape) - x

            return jax.tree.map(delta_of, p)

        return jax.jit(
            self.process_group.shard_map(
                local, in_specs=P(ALL_AXES), out_specs=P(ALL_AXES)
            )
        )

    # -- averager thread -----------------------------------------------------

    def _negotiate(self, ready: bool) -> bool:
        """Group MIN of per-rank readiness (the reference's 1-byte MIN
        allreduce abort negotiation, ``async_model_average.py:272-305``).

        Single-controller: the min over ranks is local.  Multi-process: every
        process's averager contributes each cycle (aborted ranks contribute 0
        but keep negotiating), so the agreed result keeps the collective
        sequence identical on all processes.
        """
        if jax.process_count() == 1:
            return bool(ready)
        from jax.experimental import multihost_utils

        import numpy as np

        flags = multihost_utils.process_allgather(np.int32(1 if ready else 0))
        return bool(flags.min())

    def _cycle(self, stop_event=None, wait: bool = True):
        """One averaging cycle.  ``wait=False`` (the background thread's mode)
        dispatches the delta program and publishes the in-flight result
        without ever blocking.  ``wait=True`` (manual / test calls) blocks
        until the delta lands, for determinism."""
        stop_event = stop_event or self._stop_event
        # Multi-process: negotiation is itself a collective, and warmup steps
        # contain gradient allreduces — negotiating mid-warmup would interleave
        # collectives in different orders across processes and hang the job.
        # Every process gates on its *local* warmup completion, making the
        # per-process collective sequence identical: W warmup allreduces, then
        # negotiate rounds (which rate-match by blocking on the slowest peer).
        if jax.process_count() > 1 and self._published_step < self.warmup_steps:
            return
        with self._cycle_lock:
            with self._pending_lock:
                # An unconsumed delta (still in flight, or landed but no
                # step has folded it yet) makes a new dispatch pure waste —
                # the average would be displaced unconsumed.  Folded into
                # the negotiated ``ready`` flag rather than an early return
                # so the multi-process collective sequence stays in
                # lockstep (every rank still negotiates every cycle).
                slot_free = self._pending is None
            ready = (
                self._status == "running"
                and not stop_event.is_set()
                and self._latest is not None
                and self._published_step >= self.warmup_steps
                and slot_free
            )
            if not self._negotiate(ready):
                return
            if self._jit_average is None:
                # AOT-compile OUTSIDE the dispatch lock: the first cycle would
                # otherwise hold the lock for the full XLA compile of the
                # average program, stalling every training-step dispatch for
                # seconds.  The lock below then covers only the enqueue.
                self._jit_average = self._build_average().lower(self._latest).compile()
            with self.host_dispatch_lock:
                with self._pending_lock:
                    gen = self._fold_generation
                    latest = self._latest
                delta = self._jit_average(latest)
            if wait:
                jax.block_until_ready(delta)
            with self._pending_lock:
                if self._status == "running" and gen == self._fold_generation:
                    if self._pending is not None:
                        # An unconsumed previous delta is displaced — drain
                        # it below so no untracked program outlives the cycle.
                        # (Unreachable in the background mode now that
                        # ``slot_free`` gates the dispatch; kept for manual
                        # _cycle() callers.)
                        self._orphans.append(self._pending[1])
                    self._pending = (gen, delta)
                    self._pending_ready = bool(wait)  # wait=True: landed
                else:
                    # Publish suppressed (abort or a racing fold): the
                    # orphaned program still drains below, so abort()'s
                    # exclusive-device-time contract holds — releasing
                    # ``_cycle_lock`` must imply the device is quiet.
                    self._orphans.append(delta)
            self._drain_orphans()

    def _drain_orphans(self):
        """Wait out any dropped-while-in-flight delta programs.  Called from
        the averager thread and abort() — never from the step dispatch path."""
        with self._pending_lock:
            orphans, self._orphans = self._orphans, []
        for delta in orphans:
            try:
                jax.block_until_ready(delta)
            except Exception:
                pass  # a failed orphan is quiet by definition

    def _watch_pending(self, stop_event):
        """Mark the pending delta ready once its buffers land — on THIS
        thread, so the training-step path never queries the backend.

        Polls one representative leaf: all outputs of a single executable
        become ready together when it completes, so one probe stands for the
        tree.  Runs lock-free between probes; bails when
        the pending slot changes under it (fold consumed it / abort)."""
        poll_s = min(0.01, self.sync_interval_ms / 1000.0 / 4)
        warned = False
        t0 = None
        while not stop_event.is_set():
            with self._pending_lock:
                if self._pending is None or self._pending_ready:
                    return
                gen, delta = self._pending
            leaf = next(
                (l for l in jax.tree.leaves(delta) if hasattr(l, "is_ready")),
                None,
            )
            try:
                landed = leaf is None or leaf.is_ready()
            except Exception as e:
                with self._pending_lock:
                    if self._pending is not None and self._pending[0] == gen:
                        self._orphans.append(self._pending[1])
                        self._pending = None
                        self._pending_ready = False
                self._log_fold_failure("pending delta unusable", e)
                return
            if landed:
                with self._pending_lock:
                    if self._pending is not None and self._pending[0] == gen:
                        self._pending_ready = True
                return
            import time as _time

            if t0 is None:
                t0 = _time.monotonic()
            elif not warned and _time.monotonic() - t0 > 30.0:
                warned = True
                logging.getLogger(__name__).warning(
                    "async model average: delta in flight >30s — device "
                    "stalled? averaging is paused until it lands"
                )
            stop_event.wait(poll_s)

    def _run(self, stop_event, wake):
        while True:
            wake.wait(self.sync_interval_ms / 1000.0)
            wake.clear()
            if stop_event.is_set():
                return
            self._cycle(stop_event, wait=False)
            self._watch_pending(stop_event)

    def _ensure_thread(self):
        if self._shutdown:
            return
        if self._thread is None or not self._thread.is_alive():
            # Fresh events per thread: a stuck old thread keeps its own (set)
            # stop event, so it can never be revived by a new spawn.
            self._stop_event = threading.Event()
            self._wake = threading.Event()
            self._thread = threading.Thread(
                target=self._run,
                args=(self._stop_event, self._wake),
                daemon=True,
                name="bagua-async-averager",
            )
            self._thread.start()

    # -- host-side engine hooks ---------------------------------------------

    def _log_fold_failure(self, what: str, exc: Exception) -> None:
        self.folds_failed += 1
        logging.getLogger(__name__).warning(
            "async model average: %s (%s: %s); the average was skipped "
            "(folds_failed=%d)", what, type(exc).__name__, exc, self.folds_failed
        )

    def host_pre_dispatch(self, state):
        """Fold a landed average into the params about to be dispatched.

        ZERO backend queries on this path: readiness is a plain bool set by
        the averager thread (``_watch_pending``), and a delta still in
        flight simply stays pending for a later step (the training loop
        never waits on the averager, the reference's defining property,
        async_model_average.py:208-230)."""
        with self._pending_lock:
            if self._pending is None or not self._pending_ready:
                return state
            gen, delta = self._pending
            if gen != self._fold_generation:
                # Snapshot predates an intervening fold — applying it would
                # double-count that fold's correction.  Drop (to the orphan
                # list: it may still be in flight, and only the averager /
                # abort may wait on it); a fresh delta comes next cycle.
                self._orphans.append(delta)
                self._pending = None
                self._pending_ready = False
                return state
            self._pending = None
            self._pending_ready = False
        try:
            folded = self._jit_fold(state.params, delta)
        except Exception as e:
            # Dispatch-time (structural) failure: param tree / sharding
            # mismatch, e.g. after an in-place model swap.  Loud, counted —
            # a permanent mismatch would otherwise silently stop averaging.
            self._log_fold_failure("fold dispatch failed", e)
            return state
        with self._pending_lock:
            self._fold_generation += 1
            # Retarget the snapshot source at the folded params so a cycle
            # racing this fold can never capture the pre-fold tree.
            self._latest = folded
        self.folds_applied += 1
        return state._replace(params=folded)

    def host_post_dispatch(self, state, step: int) -> None:
        self._latest = state.params
        self._published_step = step
        self._ensure_thread()

    # -- control (reference ``:232-305``) ------------------------------------

    def abort(self):
        """Stop averaging; waits for any in-flight average to drain (both the
        cycle's dispatch and its device-side execution) and discards the
        undelivered result — callers rely on exclusive device time after
        abort() returns (e.g. a timed benchmark window)."""
        if self._status != "running":
            return
        self._status = "aborted"
        with self._cycle_lock:  # drain: in-flight cycle's dispatch first
            with self._pending_lock:
                if self._pending is not None:
                    self._orphans.append(self._pending[1])
                    self._pending = None
                self._pending_ready = False
            self._drain_orphans()  # device-side drain, failures included

    def resume(self):
        self._status = "running"

    def host_shutdown(self):
        """Stop the averager thread permanently (end of training)."""
        self._shutdown = True
        self._stop_event.set()
        if self._thread is not None:
            self._wake.set()
            self._thread.join(timeout=5.0)
            self._thread = None

    # -- traced stages -------------------------------------------------------

    def transform_gradients(self, grads, params, state, ctx: StepContext):
        if self.warmup_steps > 0:
            # Warmup phase: plain gradient allreduce (reference ``:120-141``
            # routes warmup steps through the centralized op).
            def avg(g):
                flats = ctx.plan.bucketize(g)
                return ctx.plan.debucketize(
                    [allreduce_inplace(f, op=ReduceOp.AVG) for f in flats], g
                )

            grads = jax.lax.cond(
                ctx.step < self.warmup_steps, avg, lambda g: g, grads
            )
        return grads, params, state


class AsyncModelAverageAlgorithm(Algorithm):
    def __init__(
        self,
        peer_selection_mode: str = "all",
        sync_interval_ms: int = 500,
        warmup_steps: int = 0,
    ):
        self.peer_selection_mode = peer_selection_mode
        self.sync_interval_ms = sync_interval_ms
        self.warmup_steps = warmup_steps

    def reify(self, process_group) -> AsyncModelAverageAlgorithmImpl:
        return AsyncModelAverageAlgorithmImpl(
            process_group,
            peer_selection_mode=self.peer_selection_mode,
            sync_interval_ms=self.sync_interval_ms,
            warmup_steps=self.warmup_steps,
        )
