#!/usr/bin/env python3
"""Benchmark: VGG16 synthetic training throughput per chip, per algorithm.

Mirrors the reference's ``examples/benchmark/synthetic_benchmark.py`` (VGG16,
batch 32 per worker, synthetic ImageNet-shaped data) whose CI gates every
algorithm with an individual floor
(``.buildkite/scripts/benchmark_master.sh:81-83``): gradient_allreduce 185,
bytegrad 180, decentralized 150, low_precision_decentralized 115, qadam 165,
async 190 img/sec/GPU.

Emission protocol (shared with bench_bert.py, see ``_bench_common``): JSON
lines on stdout, last line authoritative.  The headline metric
(gradient_allreduce) is emitted provisionally as soon as its first timed step
lands, then one line per additional algorithm as the deadline allows, and the
headline is re-emitted LAST so the driver's last-line parse always sees the
reference's primary gate.  A failure of the headline algorithm is the
process's failure (non-zero exit); only the other five are isolated.
"""

import os
import time

from _bench_common import BenchHarness

HARNESS = BenchHarness("vgg16_img_per_sec_per_chip", "img/s/chip")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bagua_tpu.observability.goodput import chip_peak_flops

# Reference per-algorithm floors (img/sec/GPU, BASELINE.md:11-16).
ALGORITHM_FLOORS = {
    "gradient_allreduce": 185.0,
    "bytegrad": 180.0,
    "qadam": 165.0,
    "decentralized": 150.0,
    "low_precision_decentralized": 115.0,
    "async": 190.0,
}
HEADLINE = "gradient_allreduce"

# VGG16 at 224x224: ~15.5 GFLOP/img forward; fwd+bwd ~= 3x forward.
VGG16_TRAIN_GFLOP_PER_IMG = 15.5 * 3


SMOKE = False  # set by main() when the config differs from the measured one


def _line(value, algorithm, provisional=False):
    extra = {"algorithm": algorithm}
    if SMOKE:
        # A shrunken config must not emit ratios against the 224px floors or
        # the full-size GFLOP constant — mark the line instead.
        extra["config"] = "SMOKE (non-reference shapes)"
        extra["vs_baseline"] = None
    else:
        extra["vs_baseline"] = round(value / ALGORITHM_FLOORS[algorithm], 3)
        # a device_kind outside the peak table (the CPU included) raises
        extra["mfu"] = round(
            value * VGG16_TRAIN_GFLOP_PER_IMG * 1e9 / chip_peak_flops(), 3)
    HARNESS.emit(value, provisional=provisional, extra=extra)


def _bench_algorithm(name, make_ddp, params, batch, deadline, max_iters=12,
                     on_first_step=None):
    """Compile + warmup + timed loop for one algorithm.  Returns img/s/chip
    (global batch normalised by group size), or None when an algorithm other
    than the headline failed — one broken relaxation must not sink the other
    lines; the headline's exception propagates.  ``on_first_step(rate)``
    fires after the first timed step (the headline's provisional line)."""
    x, y = batch
    ddp = None
    try:
        ddp = make_ddp(name)
        state = ddp.init(params)
        state, losses = ddp.train_step(state, (x, y))  # compile + settle
        jax.block_until_ready(losses)
        # Second warmup step: the first step's output state carries committed
        # NamedShardings + XLA-chosen layouts, a different jit signature than
        # ddp.init's fresh arrays — step 2 compiles the steady-state
        # executable (a fixed point: step 3+ reuse it).  Timing must start
        # after BOTH compiles; the reference's synthetic_benchmark.py warms
        # 10 full iterations before its timed window.
        state, losses = ddp.train_step(state, (x, y))
        jax.block_until_ready(losses)
        HARNESS.note(f"{name}: compile + warmup done (2 steps)")
        # Reset attribution so the snapshot covers ONLY the timed window —
        # the warmup steps' compile seconds would otherwise swamp it.
        ddp.host_overhead_snapshot(reset=True)
        t0 = time.perf_counter()
        state, losses = ddp.train_step(state, (x, y))
        jax.block_until_ready(losses)
        first = time.perf_counter() - t0
        if on_first_step is not None:
            on_first_step(x.shape[0] / first / ddp.group.size)
        n_iters = 1  # the timed window includes the first step
        while n_iters < max_iters and time.perf_counter() < deadline:
            state, losses = ddp.train_step(state, (x, y))
            n_iters += 1
        jax.block_until_ready(losses)
        elapsed = time.perf_counter() - t0
        HARNESS.note(f"{name}: {n_iters} steps in {elapsed:.2f}s")
        # Host-side attribution (VERDICT r4 #3): where each step's wall time
        # went OUTSIDE device execution — pre-dispatch fold, lock waits,
        # enqueue, post-dispatch.  The async 183 img/s mystery lived here.
        HARNESS.note(f"{name}: host overhead {ddp.host_overhead_snapshot()}")
        return x.shape[0] * n_iters / elapsed / ddp.group.size
    except Exception as e:  # noqa: BLE001 — per-algorithm isolation
        if name == HEADLINE:
            raise
        HARNESS.note(f"{name}: FAILED {type(e).__name__}: {e}")
        return None
    finally:
        if ddp is not None:
            ddp.shutdown()  # stop algorithm background threads (async averager)


def main():
    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.vgg import init_vgg16, vgg_loss_fn

    deadline = HARNESS.t0 + float(os.environ.get("BENCH_DEADLINE_SEC", "420"))
    HARNESS.note(f"jax ready: {len(jax.devices())} {jax.devices()[0].platform} device(s)")

    group = bagua_tpu.init_process_group()
    n = group.size
    # Smoke-test overrides (CPU CI): the measured configuration is the
    # default 32 x 224x224, matching the reference benchmark exactly.
    per_chip_batch = int(os.environ.get("BENCH_BATCH_PER_CHIP", "32"))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", "224"))
    global SMOKE
    SMOKE = (per_chip_batch, image_size) != (32, 224)
    global_batch = per_chip_batch * n

    model, params = init_vgg16(
        jax.random.PRNGKey(0), image_size=image_size, num_classes=1000,
        compute_dtype=jnp.bfloat16,
    )
    loss_fn = vgg_loss_fn(model)

    def make_ddp(name):
        return DistributedDataParallel(
            loss_fn, optax.sgd(0.01, momentum=0.9), build_algorithm(name, lr=0.01),
            process_group=group,
        )

    HARNESS.note("model initialized")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(global_batch, image_size, image_size, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, size=(global_batch,)).astype(np.int32))
    batch = (x, y)

    # Headline first: guarantees the primary gate lands even if the deadline
    # cuts the per-algorithm sweep short; a provisional line goes out the
    # moment its first timed step completes.
    headline = _bench_algorithm(
        HEADLINE, make_ddp, params, batch, deadline,
        on_first_step=lambda rate: _line(rate, HEADLINE, provisional=True),
    )
    _line(headline, HEADLINE, provisional=True)

    # Per-algorithm sweep (reference gates all six): only start an algorithm
    # when enough budget remains for its compile (~40s cold) + a few steps.
    for name in ALGORITHM_FLOORS:
        if name == HEADLINE:
            continue
        if time.perf_counter() > deadline - 75.0:
            HARNESS.note(f"skipping {name}: <75s of budget left")
            continue
        value = _bench_algorithm(name, make_ddp, params, batch, deadline, max_iters=8)
        if value is not None:
            _line(value, name)

    # Authoritative last line = the reference's primary gate.
    _line(headline, HEADLINE)


if __name__ == "__main__":
    main()
