"""High-level training loop convenience (the Lightning-``BaguaStrategy``
analog — the reference integrates via pytorch-lightning, tested at
``tests/pytorch_lightning/test_bagua_strategy.py``; here the equivalent
one-stop entry is a small Trainer that wires the DDP engine, autotune,
watchdog, speed metrics and checkpointing together)."""

import contextlib
import itertools
import logging
import os
import time
from typing import Callable, Iterable, Optional, Tuple

import jax

from bagua_tpu.observability import cold_start

# the module's body is a cold event, counted from here: the package's own
# import has ended by now and is a span of its own
with cold_start.cold_host_span("setup", "import", detail=__name__):
    from bagua_tpu.algorithms.base import Algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.observability import StepTimer, Watchdog
    from bagua_tpu.observability.annotations import fit_step_span, host_span, timed_host_span
    from bagua_tpu.service.autotune_session import AutotuneSession

logger = logging.getLogger(__name__)


def _from_plain(value, build):
    """What ``Trainer(telemetry=, health_monitor=)`` make of a plain value,
    the kind a launcher's config file can carry: ``True`` is ``build({})``
    (the class's defaults), a dict ``build`` of the constructor's keywords;
    an instance or ``None`` is taken as it is."""
    if value is None or value is False:
        return None
    if value is True or isinstance(value, dict):
        return build({} if value is True else dict(value))
    return value


def _build_health_monitor(telemetry):
    """``HealthMonitor`` from keywords: bound to the hub, no action unless
    given; ``config`` may itself be a dict of ``HealthConfig``'s fields."""
    from bagua_tpu.observability.health import HealthConfig, HealthMonitor

    def build(kwargs):
        if isinstance(kwargs.get("config"), dict):
            kwargs["config"] = HealthConfig(**kwargs["config"])
        kwargs.setdefault("telemetry", telemetry)
        return HealthMonitor(**kwargs)

    return build


class Trainer:
    """Minimal fit loop.

    Args:
        loss_fn, optimizer, algorithm, process_group: as for
            :class:`~bagua_tpu.ddp.DistributedDataParallel`.
        ckpt_dir: if set, checkpoints every ``ckpt_interval`` steps and
            auto-resumes from the latest checkpoint on startup.
        snapshot_dir: if set, the resilience subsystem snapshots the train
            state every ``snapshot_every`` steps *off the critical path*
            (:class:`~bagua_tpu.resilience.AsyncSnapshotter`), installs a
            SIGTERM preemption watcher that drains the in-flight step and
            forces a final snapshot before a clean exit, and auto-resumes
            from the newest complete snapshot on startup — carrying the
            tuned bucket plan over.  ``BAGUA_SNAPSHOT_EVERY`` overrides the
            cadence; a run stopped by preemption sets ``self.preempted``.
        autotune_model_name: if set (and the autotune service is reachable),
            runs the report/ask/re-bucket cycle.
        watchdog_timeout_s: hang detector (0 disables;
            ``BAGUA_WATCHDOG_TIMEOUT_S`` in the environment overrides a
            non-zero value).
        profile_dir: if set, captures ONE xprof trace of fit-loop iterations
            ``[profile_steps[0], profile_steps[1])`` (half-open; default
            iterations 10-12, past compilation) into this directory.  One
            capture per Trainer, even across multiple ``fit()`` calls; a
            window cut short by the end of an epoch is closed and kept.  The
            ``fit`` call that held the capture reduces it before it returns
            (:func:`~bagua_tpu.observability.trace_analysis.summarize_capture`:
            the step's device time by phase, the exchange operation by
            operation, host spans, idle gaps by host span) into
            ``profile_summary``.
        telemetry: opt-in
            :class:`~bagua_tpu.observability.telemetry.Telemetry` hub, passed
            through to the DDP engine.  Beside an instance or ``None`` it
            takes what a launcher's config file can carry: ``True`` (the
            class's defaults: the hub with the flight recorder as
            ``BAGUA_FLIGHT_RECORDER`` has it, no JSONL, no tracer, no
            sentinel, no goodput meter) or a dict of the constructor's
            keywords.  A hub the trainer built is closed by :meth:`close`.
            The trainer additionally tags the
            watchdog's heartbeats with the fit loop's phase (``data`` while
            pulling the next batch) and points the watchdog's hang dump at
            the hub's snapshot, so a timeout names the step/phase/variant the
            job died in.
        health_monitor: opt-in
            :class:`~bagua_tpu.observability.health.HealthMonitor`, passed
            through to the DDP engine (which computes the in-graph health
            scalars and feeds the detector each step).  ``True`` builds one
            with the default ``HealthConfig`` and no action registered, a
            dict is the constructor's keywords (``config`` may be a dict of
            ``HealthConfig``'s fields); either is bound to the hub.
            When a snapshotter
            is configured the trainer registers
            :class:`~bagua_tpu.observability.health.SnapshotOnAnomalyAction`
            so the first anomaly leaves a restorable pre-divergence state.
        gang_window: if > 0 (and a telemetry hub is attached), every
            ``gang_window`` fit steps this rank pushes its step summary
            through the rendezvous KV and rank 0 exports the joined gang
            view (:class:`~bagua_tpu.observability.aggregate.GangAggregator`
            — best-effort: a missing/unreachable KV degrades to a
            local-only view with zero training-path impact).
        autopilot: opt-in
            :class:`~bagua_tpu.autopilot.GangAutopilot` bound to this
            trainer's DDP engine.  The fit loop ticks it once per step with
            the step's mean loss; the controller may switch the gang's
            algorithm/precision configuration (the returned state replaces
            the loop's) — every move statically verified before dispatch.
    """

    @cold_start.cold_event("setup", "trainer")
    def __init__(
        self,
        loss_fn: Callable,
        optimizer,
        algorithm: Algorithm,
        process_group=None,
        ckpt_dir: Optional[str] = None,
        ckpt_interval: int = 1000,
        snapshot_dir: Optional[str] = None,
        snapshot_every: int = 10,
        snapshot_keep: int = 2,
        autotune_model_name: Optional[str] = None,
        watchdog_timeout_s: float = 300.0,
        dp_filter=None,
        profile_dir: Optional[str] = None,
        profile_steps: Tuple[int, int] = (10, 13),
        telemetry=None,
        health_monitor=None,
        gang_window: int = 0,
        dp_axis=None,
        fsdp_axis=None,
        tp_axis=None,
        autopilot=None,
    ):
        # Persistent compile cache: a restarted trainer deserializes the step
        # executable instead of paying the XLA compile again.
        from bagua_tpu.env import setup_compile_cache

        logger.info("persistent compilation cache at %s", setup_compile_cache())
        from bagua_tpu.observability.telemetry import Telemetry

        given, telemetry = telemetry, _from_plain(telemetry, lambda kw: Telemetry(**kw))
        self.telemetry = telemetry
        self._owns_telemetry = telemetry is not given  # built here: closed here
        health_monitor = self.health_monitor = _from_plain(
            health_monitor, _build_health_monitor(telemetry))
        self.ddp = DistributedDataParallel(
            loss_fn, optimizer, algorithm, process_group=process_group,
            dp_filter=dp_filter, telemetry=telemetry,
            health_monitor=health_monitor,
            dp_axis=dp_axis, fsdp_axis=fsdp_axis, tp_axis=tp_axis,
        )
        # The engine is constructed here, so a pre-built controller can't be
        # bound to it yet: accept a factory (``lambda ddp: GangAutopilot(ddp,
        # cost_model, ...)``) or an instance whose ``ddp`` we (re)bind.
        if callable(autopilot) and not hasattr(autopilot, "tick"):
            autopilot = autopilot(self.ddp)
        elif autopilot is not None:
            autopilot.ddp = self.ddp
        self.autopilot = autopilot
        self.gang_window = int(gang_window)
        self.gang = None  # built lazily in init_state (needs the KV client)
        self.ckpt_dir = ckpt_dir
        self.ckpt_interval = ckpt_interval
        self.autotune_model_name = autotune_model_name
        self.timer = StepTimer(speed_meter=self.ddp.speed_meter)
        self.watchdog = (
            Watchdog(watchdog_timeout_s).start() if watchdog_timeout_s > 0 else None
        )
        if self.watchdog is not None and telemetry is not None:
            # hub heartbeats carry the step phase; hang dumps carry the hub's
            # snapshot (step, phase, variant, recompile report), the flight
            # ring, and a hang event through the hub's sinks
            telemetry.bind_watchdog(self.watchdog)
            if self.watchdog.digest_pusher is None:
                self.watchdog.digest_pusher = self._push_flight_digest
        self._session: Optional[AutotuneSession] = None
        #: per-rank losses of the most recent ``fit`` step (a device array,
        #: not synced; None before the first step)
        self.last_losses = None
        # xprof capture of steps [a, b) once compilation has settled
        # (docs/performance.md "profile -> fix -> repeat").
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        self._profiler = None
        self._profiled = False  # one capture per Trainer, across fit() calls
        self._summary_due = False  # a capture has stopped and is not reduced yet
        self._startup = None  # the set-up's partition, kept when the first ``fit`` call ends
        #: ``trace_analysis.summarize_capture`` of the capture, made at the
        #: end of the ``fit`` call that held it (None before, and without
        #: ``profile_dir``)
        self.profile_summary = None
        # the join from a captured operation to its scope labels runs through
        # the compiled step's text, which the engine keeps only when asked
        self.ddp.keep_step_text = profile_dir is not None
        if profile_dir is not None:
            # ... and the text has to be this program's: the persistent cache
            # keys a program without its metadata, so an executable compiled
            # for one that differs only in its labels (the same step before a
            # scope was added or dropped) would answer, labels and all.  A
            # profiling process keys its compiles with the metadata in.
            jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        # Resilience: async snapshotter + preemption watcher (tentpole).
        self.snapshot_dir = snapshot_dir
        self.snapshotter = None
        self.preemption = None
        self.preempted = False
        self.resume_result = None
        self._closed = False
        if snapshot_dir:
            from bagua_tpu.env import get_snapshot_every
            from bagua_tpu.resilience import AsyncSnapshotter, PreemptionWatcher

            every = get_snapshot_every() or snapshot_every
            self.snapshotter = AsyncSnapshotter(
                snapshot_dir, every,
                world_size=self.ddp.group.size,
                telemetry=telemetry,
                keep=snapshot_keep,
                # the live bucket plan rides every manifest so resume never
                # cold-starts the planner
                manifest_extra_fn=lambda: {"plan": self.ddp.export_plan_payload()},
            )
            if health_monitor is not None:
                from bagua_tpu.observability import SnapshotOnAnomalyAction

                # first anomaly => blocking snapshot of the pre-divergence
                # state (fires once; see health.SnapshotOnAnomalyAction)
                health_monitor.register_action(
                    SnapshotOnAnomalyAction(self.snapshotter)
                )
            self.preemption = PreemptionWatcher()
            try:
                self.preemption.install()
            except ValueError:
                # signal handlers only install on the main thread; a trainer
                # driven from a worker thread keeps programmatic trigger()
                logger.warning("not on the main thread: preemption watcher "
                               "responds to trigger() only, not SIGTERM")

    @cold_start.cold_event("setup", "init_state")
    def init_state(self, params=None, stacked_params=None):
        state = self.ddp.init(params, stacked_params=stacked_params)
        resumed = False
        if self.snapshotter is not None:
            # Elastic resume from the newest complete snapshot (preferred
            # over the synchronous checkpoint path: the drain writes here).
            from bagua_tpu.resilience import ElasticResumeCoordinator

            coordinator = ElasticResumeCoordinator(
                self.snapshotter.store,
                rendezvous_client=self._rendezvous_client(),
                telemetry=self.telemetry,
            )
            try:
                result = coordinator.resume(
                    self.ddp, state, nonce=os.environ.get("BAGUA_ATTEMPT", "0")
                )
            except Exception as e:
                logger.warning("snapshot resume failed (%s); starting fresh", e)
                result = None
            if result is not None:
                state, resumed = result.state, True
                self.resume_result = result
        if not resumed and self.ckpt_dir:
            from bagua_tpu.checkpoint import get_latest_iteration, load_checkpoint

            it = get_latest_iteration(self.ckpt_dir)
            if it is not None:
                state, it = load_checkpoint(self.ckpt_dir, target=state)
                logger.info("resumed from checkpoint at iteration %d", it)
        if self.autotune_model_name:
            try:
                self._session = AutotuneSession(self.ddp, self.autotune_model_name)
            except Exception as e:  # service not reachable: train without tuning
                logger.warning("autotune disabled: %s", e)
        if self.gang_window > 0 and self.telemetry is not None and self.gang is None:
            from bagua_tpu.observability import GangAggregator

            # best-effort: a None client (no endpoint / single process) means
            # the aggregator runs local-only from the start
            self.gang = GangAggregator(
                self._rendezvous_client(),
                rank=jax.process_index(),
                world_size=jax.process_count(),
                window=self.gang_window,
                registry=self.telemetry.registry,
            )
        return state

    def _rendezvous_client(self):
        """A store client for the cross-rank snapshot agreement, when the
        launcher exported an endpoint and the job actually spans processes."""
        endpoint = os.environ.get("BAGUA_RDZV_ENDPOINT")
        if not endpoint or jax.process_count() <= 1:
            return None
        try:
            from bagua_tpu.distributed.rendezvous import RendezvousClient

            return RendezvousClient(
                endpoint, node_rank=int(os.environ.get("NODE_RANK", 0))
            )
        except Exception as e:
            logger.warning("rendezvous client unavailable for resume (%s)", e)
            return None

    def _push_flight_digest(self) -> bool:
        """Best-effort push of this rank's flight-ring digest through the
        rendezvous KV (retry/breaker-guarded inside; local-only degradation
        on outage).  Called from the watchdog's evidence dump and the
        preemption drain."""
        fr = getattr(self.telemetry, "flight", None) if self.telemetry else None
        if fr is None:
            return False
        from bagua_tpu.observability.flight_recorder import push_flight_digest

        return push_flight_digest(self._rendezvous_client(), fr)

    def fit(self, state, batches: Iterable, n_steps: Optional[int] = None, log_every: int = 100):
        """Run the training loop; returns the final state.  The per-rank
        losses of the last step taken are left in ``last_losses``.

        Each iteration is a ``bagua_fit`` step annotation in a profiler
        capture and the work inside it a ``bagua_host/fit/…`` span, so a gap
        of the device can be put down to what this loop was doing; the time
        in ``next()`` on ``batches`` and in the rest of the loop body outside
        ``train_step`` is counted in the engine's ``host_overhead`` under
        ``next_batch`` and ``loop``; what the dispatching thread waits for
        the device on the tracing's account (a health monitor's read where
        an action is registered) under ``health_wait``.  With a hub attached
        the call returns once every step it dispatched has been seen to
        complete and its health observed (``ddp.drain_steps``)."""
        overhead = self.ddp.host_overhead
        batches = iter(batches)
        stepped = False
        for i in itertools.count():
            with contextlib.ExitStack() as iteration:
                iteration.enter_context(fit_step_span(i))
                with timed_host_span("fit", "next_batch", overhead):
                    batch = next(batches, None)
                if batch is None or (n_steps is not None and i >= n_steps):
                    break
                if (
                    self.profile_dir is not None
                    and i == self.profile_steps[0]
                    and not self._profiled
                    and self._profiler is None
                ):
                    # with the batch in hand, so that the capture's first
                    # operation is the step's.  The iteration's annotation
                    # opened before the capture began and is not in it: open
                    # it again for the rest of the iteration.
                    iteration.close()
                    self._start_capture(state)
                    iteration.enter_context(fit_step_span(i))
                began = time.perf_counter()
                # rebinding ``state`` drops the donated arrays of the state
                # before: host work of every step, and counted here
                state, in_step, stop = self._fit_step(state, batch, log_every)
                overhead["loop"] += time.perf_counter() - began - in_step
            stepped = True
            if stop:
                return state
            if self._profiler is not None and i == self.profile_steps[1] - 1:
                self._stop_capture(state, "captured")
        if stepped:
            jax.block_until_ready(self.last_losses)
            self.ddp.drain_steps()
        if self._profiler is not None:
            # epoch ended inside the capture window: close it here (one
            # short trace kept) rather than recording every later epoch
            self._stop_capture(state, "cut at epoch end")
        if self._summary_due:
            self._summarize_capture()
        if self._startup is None:
            self._startup = cold_start.setup_snapshot()
            logger.info("%s", cold_start.format_setup(self._startup))
        return state

    def startup_report(self) -> dict:
        """Where the time before the first steps went, in seconds by class
        (:func:`~bagua_tpu.observability.cold_start.setup_snapshot`: import,
        init, the step's tracing and its compile or cache load, every other
        program compiled, the cache's hits and misses), from the process's
        record of cold events: up to the end of the first ``fit`` call, which
        logs it once, or up to now before that."""
        return self._startup or cold_start.setup_snapshot()

    def _fit_step(self, state, batch, log_every: int):
        """One iteration of :meth:`fit` after its batch: ``(state, seconds
        inside train_step, whether to stop)``."""
        if self._session and not self._session.profiled:
            # one-time measured execution-order profile for autotune
            try:
                self._session.profile_and_report(state, batch)
            except Exception as e:
                logger.warning("bucket-order profiling failed: %s", e)
                self._session.profiled = True
        n_samples = jax.tree.leaves(batch)[0].shape[0]
        with host_span("fit/train_step"), self.timer.step(n_samples):
            in_step = time.perf_counter()
            state, losses = self.ddp.train_step(state, batch)
            in_step = time.perf_counter() - in_step
        self.last_losses = losses
        if self.watchdog:
            self.watchdog.beat()
        if self._session:
            self._session.tick(n_samples)
        step = self._state_step(state)
        if self.autopilot is not None:
            # the controller may remap the state (algorithm switch) —
            # the loss sync here is what feeds its canary parity check
            with host_span("fit/autopilot"):
                jax.block_until_ready(losses)
                self.ddp.drain_steps()  # the controller reads this step's health
                state = self.autopilot.tick(state, step, float(losses.mean()))
        if self.snapshotter is not None:
            with host_span("fit/snapshot"):
                self.snapshotter.maybe_snapshot(state, step)
        if self.gang is not None:
            # window-cadenced, best-effort; off-cadence calls return
            # immediately and KV trouble degrades to a local-only view
            with host_span("fit/gang"):
                ho = self.ddp.host_overhead
                denom = max(1, int(ho.get("steps", 1)))
                self.gang.tick(
                    step, self.telemetry,
                    phase_ms={k: 1e3 * v / denom for k, v in ho.items()
                              if k != "steps"},
                )
        if self.preemption is not None and self.preemption.should_stop():
            self._drain_and_exit(state, step)
            return state, in_step, True
        if self.ckpt_dir and step % self.ckpt_interval == 0:
            from bagua_tpu.checkpoint import save_checkpoint

            with host_span("fit/checkpoint"):
                save_checkpoint(step, self.ckpt_dir, state)
        if log_every and step % log_every == 0:
            with host_span("fit/log_sync"):
                jax.block_until_ready(losses)
                logger.info(
                    "step %d loss %.5f (%.1f samples/s)",
                    step,
                    float(losses.mean()),
                    self.ddp.speed_meter.speed(30.0),
                )
        if self.telemetry is not None:
            # about to pull the next batch — a hang here is the input
            # pipeline's, not the device's
            self.telemetry.enter_phase("data")
        return state, in_step, False

    def _start_capture(self, state) -> None:
        from bagua_tpu.observability import ProfilerSession

        jax.block_until_ready(state)  # clean capture window
        self._profiler = ProfilerSession(self.profile_dir)
        self._profiler.start()
        self._profiled = True

    def _stop_capture(self, state, how: str) -> None:
        with host_span("fit/capture"):  # the drain before the stop
            jax.block_until_ready((state, self.last_losses))
        self._profiler.stop()
        self._profiler = None
        self._summary_due = True
        logger.info("xprof trace (%s) written to %s", how, self.profile_dir)

    def _summarize_capture(self) -> None:
        """Reduces the capture this ``fit`` call held, once the loop has
        drained, and leaves the compiled step's text beside it for
        ``ci/analyze_trace.py``.  A capture that cannot be reduced costs the
        summary, never the run."""
        from bagua_tpu.observability import trace_analysis

        self._summary_due = False
        try:
            hlo_text = self.ddp.step_text()
            if hlo_text:
                with open(os.path.join(self.profile_dir, trace_analysis.STEP_TEXT_FILE), "w") as f:
                    f.write(hlo_text)
            self.profile_summary = trace_analysis.summarize_capture(
                self.profile_dir, hlo_text=hlo_text)
        except Exception:
            logger.exception("the capture under %s could not be reduced", self.profile_dir)
            return
        if self.profile_summary is not None:
            logger.info("captured step: %s", trace_analysis.format_partition(self.profile_summary))

    def _state_step(self, state) -> int:
        """Completed-step count, readable on every process of the gang (the
        rank-0 slice of ``state.step`` may not be addressable here)."""
        if self.ddp._host_step is not None:
            return self.ddp._host_step
        arr = state.step
        if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
            import jax.numpy as jnp

            return int(jnp.reshape(arr.addressable_shards[0].data, (-1,))[0])
        return int(arr[0])

    def _drain_and_exit(self, state, step: int) -> None:
        """The preemption path: the in-flight step has completed (we only
        poll between steps), so drain device work, force a synchronous final
        snapshot and leave a resumable marker — the restarted gang loses
        zero steps instead of up-to-K."""
        from bagua_tpu.resilience import write_resumable_marker

        logger.warning("preemption signal received: draining at step %d", step)
        if self.telemetry is not None:
            # the goodput ledger charges everything from here to the exit
            # (block + final snapshot) to the drain bucket
            self.telemetry.enter_phase("drain")
            fr = getattr(self.telemetry, "flight", None)
            if fr is not None:
                # SIGTERM forensics: the same flight_<rank>.json + KV digest
                # a watchdog timeout would leave, so a preempted gang is
                # joinable by ci/diagnose_hang.py too
                try:
                    from bagua_tpu.env import get_dump_dir
                    from bagua_tpu.observability.flight_recorder import (
                        flight_dump_path,
                    )

                    fr.dump(
                        flight_dump_path(get_dump_dir(), fr.rank),
                        reason="sigterm",
                        telemetry=self.telemetry.snapshot(),
                    )
                    self._push_flight_digest()
                except Exception:
                    logger.exception("flight dump on preemption failed")
        jax.block_until_ready(state)
        self.ddp.drain_steps()
        try:
            self.snapshotter.force_snapshot(state, step)
            write_resumable_marker(self.snapshot_dir, step)
        except Exception:
            logger.exception("final snapshot failed; newest complete "
                             "snapshot still bounds the lost work")
        self.preempted = True

    def close(self) -> None:
        """Release background machinery: profiler, snapshotter, preemption
        handler, the hang watchdog, telemetry buffers and any algorithm
        threads (async averager).  Idempotent and exception-safe: every
        teardown runs even when an earlier one fails (a profiler that died
        mid-``fit`` must not leave the watchdog thread alive or the JSONL
        stream unflushed), and a second call is a no-op."""
        if self._closed:
            return
        self._closed = True
        for what, teardown in (
            ("profiler", self._stop_profiler),
            ("snapshotter", lambda: self.snapshotter and self.snapshotter.close()),
            ("preemption watcher", lambda: self.preemption and self.preemption.uninstall()),
            ("watchdog", self._stop_watchdog),
            ("tracer", self._flush_tracer),
            ("telemetry", self._close_telemetry),
            ("ddp", self.ddp.shutdown),
        ):
            try:
                teardown()
            except Exception:
                logger.exception("error closing %s (continuing teardown)", what)

    def _close_telemetry(self) -> None:
        """A hub the caller built is flushed and stays usable for a
        post-mortem; one built here from a plain value is closed."""
        if self.telemetry is not None:
            self.telemetry.close() if self._owns_telemetry else self.telemetry.flush()

    def _flush_tracer(self) -> None:
        """Close the open step trace (if any) and flush the span JSONL so a
        teardown mid-step still lands its last trace on disk; the tracer
        itself stays open — Telemetry.close() owns its lifecycle."""
        tracer = getattr(self.telemetry, "tracer", None) if self.telemetry else None
        if tracer is not None:
            tracer.end_step()
            tracer.flush()

    def _stop_profiler(self) -> None:
        if self._profiler is not None:  # fit() ended inside the window
            self._profiler.stop()
            self._profiler = None

    def _stop_watchdog(self) -> None:
        if self.watchdog:
            self.watchdog.stop()
            self.watchdog = None

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        # Runs on the exception path too: a fit() that raises mid-step still
        # stops the watchdog and flushes telemetry (close is exception-safe).
        self.close()
