#!/usr/bin/env python3
"""Scaling benchmark: VGG16 DP throughput per chip across mesh widths.

The BASELINE.json headline includes "scaling efficiency 8->256 chips"; this
script measures it on whatever devices the session has: for each power-of-two
width w <= n_devices it trains VGG16 (gradient_allreduce) on a w-device DP
mesh and reports img/s/chip, then emits the efficiency of the widest mesh
relative to width 1 as the authoritative last line.  On one chip it
degenerates to a width-1 measurement (efficiency 1.0); on a four-chip host
or a pod slice it produces the scaling curve.

Emission protocol shared with bench.py (`_bench_common`).  CPU smoke:
``JAX_PLATFORMS=cpu BENCH_BATCH_PER_CHIP=4 BENCH_IMAGE_SIZE=64
XLA_FLAGS=--xla_force_host_platform_device_count=8 python bench_scaling.py``.
"""

import os
import time

from _bench_common import BenchHarness

HARNESS = BenchHarness("vgg16_dp_scaling_efficiency", "ratio")

import jax
import jax.numpy as jnp
import numpy as np
import optax


def measure(width, params, model_cfg, deadline, max_iters=8):
    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.vgg import vgg_loss_fn

    model, per_chip_batch, image_size = model_cfg
    group = bagua_tpu.init_process_group(devices=jax.devices()[:width])
    ddp = DistributedDataParallel(
        vgg_loss_fn(model), optax.sgd(0.01, momentum=0.9),
        build_algorithm("gradient_allreduce"), process_group=group,
    )
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    gb = per_chip_batch * width
    x = jnp.asarray(rng.rand(gb, image_size, image_size, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, size=(gb,)).astype(np.int32))
    state, losses = ddp.train_step(state, (x, y))  # compile + settle
    jax.block_until_ready(losses)
    # second warmup step compiles the steady-state executable (committed
    # sharding + XLA layouts signature) — see the bench.py warmup note
    state, losses = ddp.train_step(state, (x, y))
    jax.block_until_ready(losses)
    n_iters = 0
    t0 = time.perf_counter()
    while n_iters < max_iters and (n_iters == 0 or time.perf_counter() < deadline):
        state, losses = ddp.train_step(state, (x, y))
        n_iters += 1
    jax.block_until_ready(losses)
    elapsed = time.perf_counter() - t0
    ddp.shutdown()
    return gb * n_iters / elapsed / width


def main():
    from bagua_tpu.models.vgg import init_vgg16

    deadline = HARNESS.t0 + float(os.environ.get("BENCH_DEADLINE_SEC", "420"))
    n = len(jax.devices())
    HARNESS.note(f"{n} {jax.devices()[0].platform} device(s)")

    per_chip_batch = int(os.environ.get("BENCH_BATCH_PER_CHIP", "32"))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", "224"))
    smoke = (per_chip_batch, image_size) != (32, 224)

    model, params = init_vgg16(
        jax.random.PRNGKey(0), image_size=image_size, num_classes=1000,
        compute_dtype=jnp.bfloat16,
    )
    cfg = (model, per_chip_batch, image_size)

    widths = []
    w = 1
    while w <= n:
        widths.append(w)
        w *= 2
    if widths[-1] != n:
        widths.append(n)

    def emit_efficiency(per_chip, provisional):
        widest = max(per_chip)
        eff = per_chip[widest] / per_chip[widths[0]]
        extra = {"widths": {str(k): round(v, 2) for k, v in per_chip.items()}}
        if smoke:
            extra["config"] = "SMOKE (non-reference shapes)"
        HARNESS.emit(round(eff, 4), provisional=provisional, extra=extra)

    per_chip = {}
    for w in widths:
        # A new width costs a fresh compile (~1-2 min cold); don't start one
        # past the deadline.
        if w != widths[0] and time.perf_counter() > deadline - 150:
            HARNESS.note(f"skipping width {w}: <150s budget left")
            break
        rate = measure(w, params, cfg, deadline)
        per_chip[w] = rate
        line = {"metric": "vgg16_img_per_sec_per_chip", "unit": "img/s/chip", "width": w}
        if smoke:
            line["config"] = "SMOKE (non-reference shapes)"
        HARNESS.note(f"width {w}: {rate:.2f} img/s/chip")
        HARNESS.emit(rate, provisional=True, extra=line)
        # Keep the last-emitted line an efficiency line at every point: the
        # process may be ended mid-sweep.
        emit_efficiency(per_chip, provisional=True)

    emit_efficiency(per_chip, provisional=False)


if __name__ == "__main__":
    main()
