"""The engine's autotune client: the register / report / re-bucket cycle of
one :class:`~bagua_tpu.ddp.DistributedDataParallel` against the autotune
service, and the measurement of bucket readiness that the service's planner
consumes.  Everything here reaches the engine through its public surface."""

import logging
import math
import re
import shutil
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bagua_tpu.env import get_rpc_breaker_cooldown_s, get_rpc_breaker_threshold
from bagua_tpu.observability.core import ProfilerSession, SpanRecorder
from bagua_tpu.observability.trace_analysis import hlo_op_labels, load_trace_events
from bagua_tpu.resilience.retry import CircuitBreaker, CircuitOpenError
from bagua_tpu.service.autotune_client import get_hyperparameters_service_client

logger = logging.getLogger(__name__)


def profile_bucket_order(
    ddp,
    state,
    batch,
    return_capture: bool = False,
    method: str = "auto",
):
    """Measure each bucket's cotangent-arrival time (seconds) — the TPU
    analog of the reference learning tensor order from measured
    backward-hook spans (``autotune_service.py:274-294``) rather than
    assuming the declaration order.

    Two measurement methods:

    * ``"single_probe"`` — ONE compiled probe computes the full backward
      pass and, per bucket, a scalar consumption of that bucket's
      gradient leaves under a ``bagua_probe/bucket=<i>`` named scope.
      One AOT compile, one traced execution under the XLA profiler; each
      bucket's arrival is the start of its earliest labeled device op,
      relative to the capture's first device op.  This reads the *actual
      schedule* — meaningful under TPU's latency-hiding scheduler, which
      places each gradient fusion as early as its data allows.  The XLA
      CPU scheduler instead places weight-gradient fusions arbitrarily
      (nothing else consumes them), so on hosts the timestamps reflect
      scheduling accidents, not readiness.
    * ``"pruned"`` — one pruned jit per bucket computing *only* that
      bucket's gradients (the rest of the backward dead-code-eliminated);
      wall time after warmup approximates the backward depth needed for
      the bucket's cotangents.  One compile per bucket, but backend
      agnostic.
    * ``"auto"`` (default) — ``single_probe`` on TPU, ``pruned``
      elsewhere.

    A bucket whose tensors sit late in the backward pass (early in the
    forward) arrives later, so sorting buckets by this time recovers the
    true readiness order — and the same numbers feed the trace-driven
    planner's arrival timeline.  Returns ``times`` aligned with
    ``plan.specs`` (with ``return_capture=True``, ``(times, capture)``
    where ``capture`` holds the probe's HLO text and trace directory for
    further analysis).

    This is a profiling pass; run it once at session start, like the
    reference's autotune warmup phase.  When the single-probe capture
    yields no labeled events (label lost to fusion, profiler
    unavailable), it falls back to the pruned probe.
    """
    assert ddp.plan is not None, "call init() first"
    if method == "auto":
        method = "single_probe" if jax.default_backend() == "tpu" else "pruned"
    if method == "pruned":
        times = _profile_bucket_order_pruned(ddp, state, batch)
        capture = {"method": "pruned_per_bucket"}
        return (times, capture) if return_capture else times
    plan = ddp.plan

    def local_probe(state, batch):
        params = jax.tree.map(lambda x: x[0], state.params)
        grads = jax.grad(ddp.loss_fn)(params, batch)
        groups = plan.group_leaves(grads)
        probes = []
        for bi, spec in enumerate(plan.specs):
            with jax.named_scope(f"bagua_probe/bucket={bi}"):
                acc = jnp.zeros((), jnp.float32)
                for s in spec.slots:
                    acc = acc + jnp.sum(groups[bi][s.name].astype(jnp.float32))
                probes.append(acc[None])
        return probes

    times = capture = None
    log_dir = tempfile.mkdtemp(prefix="bagua_probe_")
    try:
        compiled = jax.jit(
            ddp.group.shard_map(
                local_probe,
                in_specs=(P(ddp.group.all_axes), P(ddp.group.data_axes)),
                out_specs=P(ddp.group.all_axes),
            )
        ).lower(state, batch).compile()  # the one extra compile
        jax.block_until_ready(compiled(state, batch))  # settle (warmup run)
        with ProfilerSession(log_dir):
            jax.block_until_ready(compiled(state, batch))
        hlo_text = compiled.as_text()
        module, labels = hlo_op_labels(hlo_text)
        events = load_trace_events(log_dir)
        scoped = [e for e in events if e["hlo_module"] == module] or events
        probe_re = re.compile(r"bagua_probe/bucket=(\d+)")
        arrivals = {}
        for e in scoped:
            m = probe_re.search(labels.get(e["hlo_op"], ""))
            if m:
                bi = int(m.group(1))
                arrivals[bi] = min(arrivals.get(bi, math.inf), e["ts"])
        if len(arrivals) == plan.num_buckets:
            t0 = min(e["ts"] for e in scoped)
            times = [(arrivals[bi] - t0) / 1e6 for bi in range(plan.num_buckets)]
            capture = {
                "method": "single_probe",
                "hlo_text": hlo_text,
                "module": module,
                "log_dir": log_dir,
                "labeled_buckets": len(arrivals),
            }
    except Exception:  # profiler unavailable / trace shape drift
        times = None
    finally:
        if not (return_capture and times is not None):
            shutil.rmtree(log_dir, ignore_errors=True)
    if times is None:
        times = _profile_bucket_order_pruned(ddp, state, batch)
        capture = {"method": "pruned_per_bucket"}
    return (times, capture) if return_capture else times

def _profile_bucket_order_pruned(ddp, state, batch):
    """Fallback order probe: for every bucket a pruned step is jitted
    that computes *only* that bucket's gradients (XLA dead-code-eliminates
    the rest of the backward pass) and its wall time is measured after a
    compile warmup — one extra compile per bucket, no profiler needed."""
    times = []
    for spec in ddp.plan.specs:
        nameset = frozenset(slot.name for slot in spec.slots)

        def local_grads(state, batch, nameset=nameset):
            params = jax.tree.map(lambda x: x[0], state.params)
            grads = jax.grad(ddp.loss_fn)(params, batch)
            flat = jax.tree_util.tree_flatten_with_path(grads)[0]
            sel = [
                leaf for path, leaf in flat
                if jax.tree_util.keystr(path) in nameset
            ]
            return [l[None] for l in sel]

        fn = jax.jit(
            ddp.group.shard_map(
                local_grads,
                in_specs=(P(ddp.group.all_axes), P(ddp.group.data_axes)),
                out_specs=P(ddp.group.all_axes),
            )
        )
        jax.block_until_ready(fn(state, batch))  # compile + settle
        t0 = time.perf_counter()
        jax.block_until_ready(fn(state, batch))
        times.append(time.perf_counter() - t0)
    return times


class AutotuneSession:
    """Drives the autotune register/report/re-bucket cycle for one DDP engine
    (reference ``bagua_distributed.py:325-391``: register at init, report
    speed + ask every ``interval`` steps, re-bucket on change)."""

    def __init__(self, ddp, model_name: str, client=None, interval: int = 100):
        self.ddp = ddp
        self.model_name = model_name
        self.client = client or get_hyperparameters_service_client()
        self.interval = interval
        self._step = 0
        self.completed = False
        # register the current plan's tensors, declaring the wire dtype the
        # initial speed reports will be measured under
        decls = [td for bucket in ddp.plan.declarations() for td in bucket]
        self.client.register_tensors(
            model_name, decls,
            current_wire_bf16=(
                getattr(ddp.impl, "wire_dtype", None) == jnp.dtype(jnp.bfloat16)
            ),
            current_overlap=ddp.overlap_enabled,
        )
        self.spans = SpanRecorder()
        # Until profile_and_report runs, the service falls back to the
        # registration order — which IS the plan's order — so nothing is lost
        # relative to round-1's (circular) plan-order report.
        self.profiled = False
        # Mid-run service flaps degrade the session to its current local
        # hyperparameters instead of crashing the step loop: report/ask are
        # retried (client-level, see autotune_client), and once the breaker
        # opens the tick becomes a fast no-op until the cooldown.
        self._breaker = CircuitBreaker(
            failure_threshold=get_rpc_breaker_threshold(),
            cooldown_s=get_rpc_breaker_cooldown_s(),
            name="autotune",
        )

    def profile_and_report(self, state, batch) -> None:
        """Measure the real per-bucket gradient-readiness order and ship it
        to the service (reference: OTel ``tensor_ready`` spans from backward
        hooks, ``autotune_service.py:274-294``).  One extra compile per
        bucket; call once when training starts (the Trainer does)."""
        times = profile_bucket_order(self.ddp, state, batch)
        self.spans.record_measured_order(self.ddp.plan, times)
        self.spans.report_to_autotune(self.client, self.model_name)
        self.profiled = True

    def report_wire_timings(self, analysis, hierarchical: Optional[bool] = None) -> None:
        """Ship a device-trace analysis
        (:func:`~bagua_tpu.observability.trace_analysis.analyze_trace`) to
        the service as per-bucket ``bucket_wire`` spans — the measured wire
        timings the service-side planner fits its α–β cost model on.  Call
        after a profiled window of real training steps; each call refines
        the model with the live plan's operating point."""
        if hierarchical is None:
            hierarchical = bool(getattr(self.ddp.impl, "hierarchical", False))
        # Sharded-update algorithms exchange gradients by reduce-scatter, so
        # their bucket_wire spans calibrate the planner's rs leg, not flat.
        leg = "rs" if getattr(self.ddp.impl, "sharded_update", False) else None
        self.spans.record_wire_timings(
            self.ddp.plan, analysis,
            intra_size=self.ddp.group.intra_size,
            hierarchical=hierarchical,
            leg=leg,
        )
        self.spans.report_to_autotune(self.client, self.model_name)

    def tick(self, n_samples: int) -> None:
        """Call once per training step with the number of samples processed."""
        self.ddp.speed_meter.record(n_samples)
        self._step += 1
        if self.completed or self._step % self.interval != 0:
            return
        # The service samples a check board and only tunes once every rank in
        # [0, world_size) has reported for an iteration — on multi-process
        # runs each controller must therefore report its own process index,
        # not a constant (reference reports torch rank, ``bagua_distributed.py:358``).
        rank = jax.process_index()
        try:
            self._breaker.before_call()
            self.client.report_metrics(
                self.model_name, rank, self._step, self.ddp.speed_meter.speed(60.0)
            )
            hp, self.completed = self.client.ask_hyperparameters(
                self.model_name, rank, self._step
            )
        except CircuitOpenError:
            return  # breaker open: fast no-op until the cooldown expires
        except (OSError, ConnectionError) as e:
            # The client already retried with backoff; a surfaced failure
            # means the service is down — record it (opens the breaker after
            # N consecutive flaps) and keep training on current hps.
            self._breaker.record_failure()
            logger.warning(
                "autotune service unreachable at step %d (%s); keeping "
                "current hyperparameters", self._step, e,
            )
            return
        self._breaker.record_success()
        self._apply(hp)

    def _apply(self, hp) -> None:
        if getattr(self.ddp.impl, "holds_bucketized_state", False):
            return  # cannot re-bucket this algorithm
        plan = self.ddp.plan_from_declarations(hp.buckets) if hp.buckets else None
        if plan is not None:
            self.ddp.rebucket(
                plan,
                predicted_exposed_ms=getattr(hp, "predicted_exposed_ms", None),
            )
        knobs = {"hierarchical": hp.is_hierarchical_reduce}
        # Opt-in wire-dtype knob: only algorithms exposing ``wire_dtype``
        # (gradient_allreduce) participate; for the rest the dimension is a
        # no-op and the optimizer sees a flat response along it.
        # ``hp.wire_bf16 is None`` = the service is not tuning this dimension
        # — a user-configured wire_dtype must then be left untouched.
        if hp.wire_bf16 is not None and hasattr(self.ddp.impl, "wire_dtype"):
            knobs["wire_dtype"] = jnp.dtype(jnp.bfloat16) if hp.wire_bf16 else None
        # Execution-mode knob, same tri-state contract as wire_bf16: the
        # capability report decides which algorithms accept it.  Restricted
        # to gradient-mode algorithms: weight/post_step algorithms shape
        # their bucket *plan* by execution mode (mega-bucket vs per-size),
        # so flipping them mid-training would need a re-plan — out of the
        # tuner's cheap-knob contract.  ``hp.overlap is None`` = dimension
        # not tuned, leave a user-configured mode untouched.
        cap = self.ddp.impl.overlap_capability()
        if hp.overlap is not None and cap.supported and cap.mode == "gradient":
            if bool(hp.overlap) != self.ddp.overlap_enabled:
                knobs["overlap"] = bool(hp.overlap)
        self.ddp.apply_knobs(knobs)
