"""Drives one cell once: builds the default ``Trainer`` on the benchmark's
seeded weights, takes the three checked warm-up steps, hands the same
trainer and state to a window of ``Trainer.fit`` fed with a fresh batch
every step, and afterwards, with the program's state freed, follows the
warm-up steps with the configuration's plain reference.

From the program it takes the system under test (``Trainer``,
``init_process_group``, ``Algorithm``, the models behind the adapters), the
engine's host counters (``ddp.host_overhead_snapshot``) and the profiler
capture ``Trainer(profile_dir=...)`` writes.  Timing, traffic, the
reduction of the trace and the comparison are the benchmark's own.
"""

import contextlib
import glob
import os
import queue
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import check, manifest, xplane

#: how many steps the feed lets ``fit`` dispatch beyond the last one seen to
#: complete: dispatch runs ahead of the device as in any ``fit`` loop, and a
#: run that ends leaves at most this many steps to drain
LOOKAHEAD = 4
#: fit-loop iterations of the window that a traced run captures
TRACE_STEPS = (20, 26)
#: steps before the window: the checked ones and one more, so that whatever
#: a later step still compiles is compiled in set-up
WARMUP_STEPS = check.CHECKED_STEPS + 1
#: rows per micro-batch and device of the reference
REFERENCE_MICRO = 8

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compilations (cache loads included) through
    ``jax.monitoring``; a listener cannot be removed one by one, so one
    counter serves the process."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1


class Watcher(threading.Thread):
    """Sees each step complete without stalling the loop: waits on the
    per-rank losses of the steps in the order they were dispatched and
    stamps the host clock as each becomes ready."""

    def __init__(self, lookahead: int):
        super().__init__(name="benchmark-watcher", daemon=True)
        self._queue = queue.Queue()
        self.slots = threading.Semaphore(lookahead)
        #: ``(time completed, mean loss over the ranks or None if it raised)``
        self.done = []

    def hand_over(self, losses) -> None:
        self._queue.put(losses)

    def run(self) -> None:
        while True:
            losses = self._queue.get()
            if losses is None:
                self._queue.task_done()
                return
            try:
                jax.block_until_ready(losses)
                stamp = time.perf_counter()
                value = float(np.mean(np.asarray(losses)))
            except Exception as e:  # the step raised on the device: a failed step
                stamp, value = time.perf_counter(), None
                print(f"step {len(self.done)} raised: {e!r}", flush=True)
            self.done.append((stamp, value))
            self.slots.release()
            self._queue.task_done()

    def drain(self) -> None:
        """Returns when every step handed over has been stamped."""
        self._queue.join()

    def close(self) -> None:
        self._queue.put(None)
        self.join()


class Feed:
    """The benchmark's batch iterator.  Batch ``k`` of a run is drawn on the
    device from ``fold_in(key, k)`` by one small jitted function whose output
    carries the step's data sharding.  Each time ``fit`` asks for the next
    batch, the step it has just dispatched is handed to the watcher."""

    def __init__(self, cell: manifest.Cell, group, key, trainer, watcher: Watcher):
        sizes, rows = cell.sizes, cell.global_batch
        # the key is an argument: closed over, it would be a constant of the
        # program, and every new seed would compile it anew
        self._key = key
        self._draw = jax.jit(
            lambda key, k: cell.adapter.draw_batch(jax.random.fold_in(key, k), rows, sizes),
            out_shardings=NamedSharding(group.mesh, P(group.data_axes)),
        )
        self._trainer = trainer
        self._watcher = watcher
        self.drawn = 0
        self._handed = 0

    def batch(self, k: int):
        return self._draw(self._key, k)

    def _hand_over(self) -> None:
        if self._handed < self.drawn:
            self._watcher.hand_over(self._trainer.last_losses)
            self._handed += 1

    def steps(self, n=None, deadline=None):
        """Batches for ``fit``: ``n`` of them, or as many as are asked for
        before ``deadline`` on ``time.perf_counter``."""
        first = self.drawn
        while True:
            self._hand_over()
            if n is not None and self.drawn - first >= n:
                return
            if deadline is not None and time.perf_counter() >= deadline:
                return
            self._watcher.slots.acquire()
            with jax.profiler.TraceAnnotation("data"):
                batch = self.batch(self.drawn)
            self.drawn += 1
            yield batch


def memory_peak_bytes(device) -> int:
    """The most the device is known to have held.  The allocator counts
    buffers (``bytes_in_use``) apart from what it reserves for the loaded
    programs' temporaries (``bytes_reserved``; PR 25 read 3.8 GB reserved
    beside 1.1 GB in use for BERT-Large at batch 32), so the peak is what
    is in use beside the largest reservation, or the buffers' own peak."""
    stats = device.memory_stats() or {}
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))


def device_section(devices) -> dict:
    peak = max(memory_peak_bytes(d) for d in devices)
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(peak),
    }


class Run:
    """One cell, one seed: ``build`` and ``setup`` (timed as set-up),
    ``window``, then ``free_program``, ``reference`` and ``numbers``."""

    def __init__(self, cell: manifest.Cell, seed: int, started: float, devices,
                 trace_dir=None, compiles: CompileCounter = None):
        self.cell, self.seed, self.started = cell, seed, started
        self.trace_dir = trace_dir
        self.compiles = compiles or CompileCounter()
        self.sizes = cell.sizes
        self.devices = list(devices)
        key = jax.random.PRNGKey(seed)
        self.params_key, self.data_key = jax.random.split(key)
        self.optimizer_spec = cell.config["optimizer"]

    # -- set-up ------------------------------------------------------------

    def build(self, built: "Run" = None) -> None:
        """The trainer and the jitted makers: everything a seed does not
        change.  ``calibrate.py`` builds once and hands the built run to the
        runs of its other seeds."""
        if built is not None:
            for name in ("group", "trainer", "make_params", "_first_grad",
                         "_update_norms", "_replicas_differ"):
                setattr(self, name, getattr(built, name))
            return
        import bagua_tpu
        from bagua_tpu.algorithms import Algorithm
        from bagua_tpu.trainer import Trainer

        cell, sizes = self.cell, self.sizes
        self.group = bagua_tpu.init_process_group(devices=self.devices)
        if self.group.size != cell.chips:
            raise SystemExit(f"group of {self.group.size} ranks for a cell of {cell.chips} chips")
        algorithm = cell.traffic["algorithm"]
        self.trainer = Trainer(
            cell.adapter.build_loss(sizes),
            check.make_optimizer(self.optimizer_spec),
            Algorithm.init(algorithm["name"], **algorithm["args"]),
            process_group=self.group,
            profile_dir=self.trace_dir, profile_steps=TRACE_STEPS,
            **cell.traffic["trainer"],
        )
        # the weights are the benchmark's: made on the device from the seed,
        # in the types the program stores them in
        self.make_params = jax.jit(lambda k: cell.adapter.to_program(
            cell.adapter.as_stored(cell.reference.init_params(k, sizes)), sizes))
        lr = self.optimizer_spec["learning_rate"]

        def f32_leaves(stacked, start):
            return {
                k: (start_leaf, stacked_leaf[0])
                for (k, start_leaf), stacked_leaf in zip(
                    check.checked_leaves(start, start).items(),
                    check.checked_leaves(stacked, start).values())
            }

        # both optimizers' first update is -lr * g exactly on a float32 leaf
        self._first_grad = jax.jit(lambda stacked, start: {
            k: (a - b) / lr for k, (a, b) in f32_leaves(stacked, start).items()})
        self._update_norms = jax.jit(lambda stacked, start: {
            k: jnp.linalg.norm((b - a).astype(jnp.float32).ravel())
            for k, (a, b) in f32_leaves(stacked, start).items()})

        def replicas_differ(params):
            def differs(x):
                bits = jax.lax.bitcast_convert_type(x, f"uint{x.dtype.itemsize * 8}")
                return jnp.any(bits != bits[:1])

            return jnp.sum(jnp.stack([differs(x) for x in jax.tree.leaves(params)]))

        self._replicas_differ = jax.jit(replicas_differ)

    def setup(self) -> None:
        """State from the seed, then the warm-up steps, the checked ones
        first, through the window's own call and feed."""
        self.watcher = Watcher(LOOKAHEAD)
        self.watcher.start()
        self.feed = Feed(self.cell, self.group, self.data_key, self.trainer, self.watcher)
        params = self.make_params(self.params_key)
        self.state = self.trainer.init_state(params)
        del params
        for k in range(WARMUP_STEPS):
            self.state = self.trainer.fit(self.state, self.feed.steps(n=1), log_every=0)
            if k == 0:
                self.first_grad = jax.device_get(
                    self._first_grad(self.state.params, self.make_params(self.params_key)))
            if k == check.CHECKED_STEPS - 1:
                self.update_norms = {k: float(v) for k, v in self._update_norms(
                    self.state.params, self.make_params(self.params_key)).items()}
        self.replica_mismatches = (
            int(self._replicas_differ(self.state.params)) if self.cell.chips > 1 else None)
        self.watcher.drain()
        self.warmup_losses = [loss for _, loss in self.watcher.done]
        if len(self.warmup_losses) != WARMUP_STEPS:
            raise RuntimeError(f"{len(self.warmup_losses)} warm-up steps seen to complete")

    # -- the window --------------------------------------------------------

    def window(self, seconds: float) -> None:
        ddp = self.trainer.ddp
        ddp.host_overhead_snapshot(reset=True)
        compiles_before = self.compiles.count
        self.window_start = time.perf_counter()
        self.setup_s = self.window_start - self.started
        self.deadline = self.window_start + seconds
        with jax.profiler.TraceAnnotation("fit"):
            self.state = self.trainer.fit(
                self.state, self.feed.steps(deadline=self.deadline), log_every=0)
        self.watcher.close()
        self.host_overhead = ddp.host_overhead_snapshot()
        self.compiles_in_window = self.compiles.count - compiles_before
        self.device = device_section(self.devices)
        self.completions = self.watcher.done[WARMUP_STEPS:]

    def free_program(self, close: bool = True) -> None:
        """Frees the program's state; the checked batches are drawn again
        for the reference."""
        if close:
            self.trainer.close()
        self.state = None
        self.feed_batches = [self.feed.batch(k) for k in range(check.CHECKED_STEPS)]

    # -- correct -----------------------------------------------------------

    def reference(self, control: bool = False):
        """``(losses, first gradient, update norms)`` of the plain reference
        over the checked steps, in the program's layout and on its float32
        leaves.  ``control``: the reference in the next lower precision."""
        cell, sizes = self.cell, self.sizes
        # rows over the cell's devices, parameters whole on each: the
        # compiler splits each micro-batch and adds the gradients up
        mesh = jax.sharding.Mesh(np.array(self.devices), ("rows",))
        whole = NamedSharding(mesh, P())
        by_rows = NamedSharding(mesh, P("rows"))
        init = jax.jit(lambda k: cell.reference.init_params(k, sizes))

        def make_params():
            return jax.device_put(cell.adapter.as_stored(init(self.params_key)), whole)

        stored = jax.eval_shape(lambda: cell.adapter.to_program(make_params(), sizes))

        def on_the_host(tree):
            """The checked leaves of a tree in the reference's layout, off
            the device: the first gradient leaves it before the second step."""
            return jax.device_get(check.checked_leaves(
                cell.adapter.to_program(tree, sizes, cast=False), stored))

        def loss_fn(params, batch):
            return cell.reference.loss(params, batch, sizes)

        micro = min(REFERENCE_MICRO * len(self.devices), cell.global_batch)
        losses, grad, delta = check.reference_steps(
            check.lower_precision(loss_fn) if control else loss_fn,
            make_params, self.feed_batches, check.make_optimizer(self.optimizer_spec),
            micro, highest=not control, place=lambda part: jax.device_put(part, by_rows),
            keep=on_the_host)
        return losses, grad, check.leaf_norms(on_the_host(delta))

    def numbers(self, ref) -> dict:
        """The program's checked steps against the reference's."""
        numbers, self.worst_leaves = check.compare(
            self.warmup_losses[:check.CHECKED_STEPS], self.first_grad, self.update_norms, *ref,
            head=self.cell.adapter.HEAD_LEAF)
        if self.replica_mismatches is not None:
            numbers["replica_mismatches"] = float(self.replica_mismatches)
        return numbers

    # -- the result --------------------------------------------------------

    def window_stats(self) -> dict:
        """What the window's completions say.  A rate is over all steps that
        completed in the window and all the time to the last of them."""
        stamps = [t for t, _ in self.completions if t <= self.deadline]
        intervals = [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
        rows = self.cell.global_batch
        stats = {
            "dispatched": len(self.completions),
            "completed_in_window": len(stamps),
            "failed": sum(1 for _, loss in self.completions
                          if loss is None or not np.isfinite(loss)),
        }
        if len(stamps) >= 2:
            stats.update(
                samples_per_s_per_chip=(
                    len(stamps) * rows / (stamps[-1] - self.window_start) / self.cell.chips),
                step_ms_p95=float(np.quantile(intervals, 0.95)),
                step_ms_median=statistics.median(intervals),
                step_ms_max=max(intervals),
            )
        return stats

    def trace(self):
        """The reduced profiler capture of a traced run, or None."""
        if self.trace_dir is None:
            return None
        paths = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError(f"the traced run left no .xplane.pb under {self.trace_dir}")
        return xplane.reduce(xplane.load(paths[0]))


def metrics_for(entries, context: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the manifest's ``entries``.  An
    end-to-end metric is the harness's own number; a per-layer metric comes
    from its reader, and one whose reader finds nothing is left out."""
    out = {}
    for entry in entries:
        name = entry["name"]
        if name in context["end_to_end"]:
            value = context["end_to_end"][name]
        else:
            value = manifest.layer_metric_reader(name)(context)
        if value is not None:
            out[name] = {"value": float(value), "unit": entry["unit"]}
    return out


@contextlib.contextmanager
def closing_run(run: Run):
    """Stops the watcher and the trainer's threads whatever happens."""
    try:
        yield run
    finally:
        watcher = getattr(run, "watcher", None)
        if watcher is not None and watcher.is_alive():
            watcher.close()
        trainer = getattr(run, "trainer", None)
        if trainer is not None:
            trainer.close()
