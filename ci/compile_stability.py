#!/usr/bin/env python3
"""Compile-stability check: the DDP train step must compile exactly ONCE.

``ddp.init`` commits the train state to the group mesh sharding so the first
step's jit signature equals every later step's (see ddp.py).  Before that
fix, step 1 recompiled the full step graph (a second ~15s VGG16 compile on
v5e, silently eaten inside the first training step).  This script drives a
few steps with compile logging hooked and asserts:

* exactly one ``local_step`` lowering/compile, and
* no post-warmup step slower than ``--stall-factor`` x the steady median
  (catches silent recompiles and layout-copy stalls regardless of logging).

It also measures the persistent compilation cache
(``JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache``): after
the timed loop the in-memory executable cache is dropped and the step
rebuilt — with the disk cache on, the rebuild deserializes instead of
recompiling, and the cold-vs-warm compile seconds land in the JSON artifact.

Runs on any backend: CPU sim for CI (``--cpu``), or the real chip.  Writes
``COMPILE_STABILITY.json`` at the repo root with per-step timings.
"""

import argparse
import json
import logging
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if REPO not in sys.path:  # runnable from any cwd without an editable install
    sys.path.insert(0, REPO)


class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.compiles = []

    def emit(self, record):
        msg = record.getMessage()
        # Loose match: tolerate the wrapper name changing ("jit(local_step)"
        # vs "local_step for pjit") but not the companion "Finished ..."
        # lines, which would double-count each compile.
        if msg.startswith("Compiling") and "local_step" in msg:
            self.compiles.append(msg[:120])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="run on the 8-device CPU sim")
    ap.add_argument(
        "--steps", type=int, default=6,
        help="training steps to time (>= 3: warmup + at least two steady)",
    )
    ap.add_argument("--stall-factor", type=float, default=5.0)
    ap.add_argument("--model", default="mlp", choices=("mlp", "vgg16"))
    ap.add_argument("--out", default=os.path.join(REPO, "COMPILE_STABILITY.json"))
    args = ap.parse_args()
    if args.steps < 3:
        ap.error("--steps must be >= 3 (warmup + at least two steady steps)")

    if args.cpu:
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from bagua_tpu.env import setup_compile_cache

    cache_dir = setup_compile_cache()
    # persist even the CPU-sim mlp step (< 1s compile) so the cold-vs-warm
    # record is meaningful on every backend
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_log_compiles", True)
    counter = _CompileCounter()
    # Root "jax" logger: survives internal module renames across JAX versions.
    logging.getLogger("jax").addHandler(counter)

    import jax.numpy as jnp
    import numpy as np
    import optax

    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.ddp import DistributedDataParallel

    group = bagua_tpu.init_process_group()
    if args.model == "vgg16":
        from bagua_tpu.models.vgg import init_vgg16, vgg_loss_fn

        size = 64 if args.cpu else 224
        net, params = init_vgg16(
            jax.random.PRNGKey(0), image_size=size, num_classes=100,
            compute_dtype=jnp.float32 if args.cpu else jnp.bfloat16,
        )
        loss_fn = vgg_loss_fn(net)
        rng = np.random.RandomState(0)
        batch = (
            jnp.asarray(rng.rand(4 * group.size, size, size, 3).astype(np.float32)),
            jnp.asarray(rng.randint(0, 100, (4 * group.size,)).astype(np.int32)),
        )
    else:
        from bagua_tpu.models.mlp import init_mlp, softmax_loss

        params = init_mlp(jax.random.PRNGKey(0), [64, 256, 10])
        loss_fn = softmax_loss
        rng = np.random.RandomState(0)
        batch = (
            jnp.asarray(rng.rand(8 * group.size, 64).astype(np.float32)),
            jnp.asarray(rng.randint(0, 10, (8 * group.size,)).astype(np.int32)),
        )

    ddp = DistributedDataParallel(
        loss_fn, optax.sgd(0.01, momentum=0.9),
        build_algorithm("gradient_allreduce"), process_group=group,
    )
    state = ddp.init(params)
    times = []
    for i in range(args.steps):
        t0 = time.perf_counter()
        state, losses = ddp.train_step(state, batch)
        jax.block_until_ready(losses)
        times.append(round(time.perf_counter() - t0, 4))

    # Cold-vs-warm persistent-cache measurement: drop the in-memory
    # executable cache and rebuild the step from scratch.  With the disk
    # cache enabled the rebuild deserializes the executable instead of
    # recompiling, so warm << cold; with it disabled the two match.  The
    # snapshot of the compile counter is taken FIRST — the warm rebuild
    # legitimately logs a second "Compiling", which is not a recompile of
    # the steady loop.
    n_compiles = len(counter.compiles)
    cold_compile_s = times[0]
    jax.clear_caches()
    ddp.drop_step_variants()
    t0 = time.perf_counter()
    state, losses = ddp.train_step(state, batch)
    jax.block_until_ready(losses)
    warm_compile_s = round(time.perf_counter() - t0, 4)
    ddp.shutdown()

    steady = times[2:] or times[1:]
    median = statistics.median(steady)
    stalled = [
        (i, t) for i, t in enumerate(times[1:], start=1)
        if t > args.stall_factor * median + 0.05
    ]
    result = {
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "model": args.model,
        "step_times_s": times,
        "local_step_compiles": n_compiles,
        "compile_cache_dir": cache_dir,
        "cold_compile_s": cold_compile_s,
        "warm_compile_s": warm_compile_s,
        "stalled_steps": stalled,
        "ok": n_compiles == 1 and not stalled,
        # Distinguish WHY the gate failed: 0 detected compiles with clean
        # timings means the log hook missed (JAX changed its message), not
        # that the invariant broke.
        "failure_reason": (
            "stall" if stalled
            else "recompile" if n_compiles > 1
            else "compile_log_not_detected" if not n_compiles
            else None
        ),
    }
    print(json.dumps(result, indent=1))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
