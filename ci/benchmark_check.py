#!/usr/bin/env python3
"""CI benchmark gate (analog of the reference's
``.buildkite/scripts/benchmark_master.sh:81-106``): for every algorithm, run
the chosen benchmark model twice and assert (a) the two runs' final losses
are EXACTLY equal (determinism gate — the reference pins exact loss values
per algorithm) and (b) throughput clears the algorithm's floor.

Models:
  mlp    — seconds-fast smoke gate (every algorithm, tiny model)
  vgg16  — the reference's headline CI workload (synthetic ImageNet shapes
           on TPU; shrunk spatial size on the CPU sim)
  bert   — BERT-style MLM encoder (shrunk config; bench_bert.py carries the
           full BERT-Large numbers)

Usage:
  real TPU, reference floors:  python ci/benchmark_check.py --model vgg16 --tpu-floors
  CPU sim (determinism gate):  python ci/benchmark_check.py --model vgg16 --cpu
  fast smoke:                  python ci/benchmark_check.py --cpu
"""

import argparse
import os
import sys
import time

# needs the package installed: `python ci/check_packaging.py` (once) or
# `pip install -e . --no-deps`

import jax

# Persistent compilation cache: the determinism gate runs every model twice,
# and the second run (plus future CI runs) should not pay the compile again.
from bagua_tpu.env import setup_compile_cache

setup_compile_cache()

QADAM_WARMUP = 5

# Reference per-algorithm VGG16 img/s/GPU floors
# (BASELINE.md / benchmark_master.sh:81-83); applied with --tpu-floors.
REFERENCE_VGG16_FLOORS = {
    "gradient_allreduce": 185.0,
    "bytegrad": 180.0,
    "decentralized": 150.0,
    "low_precision_decentralized": 115.0,
    "qadam": 165.0,
}


def build_workload(model: str, cpu: bool):
    """Returns (loss_fn, params, make_batch)."""
    import jax.numpy as jnp
    import numpy as np

    if model == "mlp":
        from bagua_tpu.models.mlp import init_mlp, mse_loss

        params = init_mlp(jax.random.PRNGKey(1), [64, 128, 16])

        def make_batch(rng, bs):
            return (
                jnp.asarray(rng.randn(bs, 64).astype(np.float32)),
                jnp.asarray(rng.randn(bs, 16).astype(np.float32)),
            )

        return mse_loss, params, make_batch

    if model == "vgg16":
        from bagua_tpu.models.vgg import init_vgg16, vgg_loss_fn

        size, classes = (32, 10) if cpu else (224, 1000)
        dtype = jnp.float32 if cpu else jnp.bfloat16
        net, params = init_vgg16(
            jax.random.PRNGKey(1), image_size=size, num_classes=classes,
            compute_dtype=dtype,
        )

        def make_batch(rng, bs):
            return (
                jnp.asarray(rng.rand(bs, size, size, 3).astype(np.float32)),
                jnp.asarray(rng.randint(0, classes, size=(bs,)).astype(np.int32)),
            )

        return vgg_loss_fn(net), params, make_batch

    if model == "bert":
        from bagua_tpu.models.bert import BertConfig, BertForPreTraining, mlm_loss_fn

        seq = 32
        cfg = BertConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position_embeddings=seq,
        )
        net = BertForPreTraining(cfg)
        params = net.init(jax.random.PRNGKey(1), jnp.zeros((2, seq), jnp.int32))["params"]

        def make_batch(rng, bs):
            return (
                jnp.asarray(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32)),
                jnp.asarray(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32)),
            )

        return mlm_loss_fn(net), params, make_batch

    raise SystemExit(f"unknown --model {model}")


def run_once(model: str, cpu: bool, algorithm: str, n_steps: int, batch: int):
    import numpy as np
    import optax

    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.ddp import DistributedDataParallel

    group = bagua_tpu.get_default_group()
    loss_fn, params, make_batch = build_workload(model, cpu)
    algo = build_algorithm(algorithm, lr=1e-3, qadam_warmup_steps=QADAM_WARMUP)
    opt = None if algorithm == "qadam" else optax.sgd(0.05)
    ddp = DistributedDataParallel(loss_fn, opt, algo, process_group=group)
    state = ddp.init(params)
    rng = np.random.RandomState(3)
    bs = batch * group.size
    # Untimed warmup long enough to compile EVERY step variant (QAdam re-jits
    # at its warmup boundary); the timed window then measures steady state.
    n_warm = (QADAM_WARMUP + 2) if algorithm == "qadam" else 2
    data = [make_batch(rng, bs) for _ in range(n_warm + n_steps)]
    for b in data[:n_warm]:
        state, losses = ddp.train_step(state, b)
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    for b in data[n_warm:]:
        state, losses = ddp.train_step(state, b)
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0
    sps = bs * n_steps / dt / group.size
    return float(losses.mean()), sps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true", help="run on the CPU simulation")
    p.add_argument("--model", default="mlp", choices=("mlp", "vgg16", "bert"))
    p.add_argument(
        "--min-throughput", type=float, default=0.0,
        help="global samples/s/chip floor (raised per algorithm by --tpu-floors)",
    )
    p.add_argument(
        "--tpu-floors", action="store_true",
        help="gate VGG16 against the reference per-algorithm img/s floors "
        "(BASELINE.md, benchmark_master.sh:81-83)",
    )
    p.add_argument("--algorithms", default=None, help="comma list; default = all deterministic")
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--batch", type=int, default=None, help="per-chip batch")
    args = p.parse_args()

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.tpu_floors and args.model != "vgg16":
        raise SystemExit(
            "--tpu-floors are VGG16 img/s numbers (BASELINE.md); "
            "use --min-throughput for other models"
        )
    if args.batch is None:
        args.batch = {"mlp": 64, "vgg16": 4 if args.cpu else 32, "bert": 8}[args.model]

    import bagua_tpu
    from bagua_tpu.algorithms import WALL_CLOCK_ALGORITHMS, GlobalAlgorithmRegistry

    bagua_tpu.init_process_group()
    if args.algorithms:
        names = args.algorithms.split(",")
    else:
        names = [
            n for n in sorted(GlobalAlgorithmRegistry.keys())
            # wall-clock schedules aren't bitwise-deterministic; "none" does
            # no DP communication at all (nothing to gate)
            if n not in WALL_CLOCK_ALGORITHMS and n != "none"
        ]
    failures = []
    for name in names:
        floor = args.min_throughput
        if args.tpu_floors:
            floor = max(floor, REFERENCE_VGG16_FLOORS.get(name, args.min_throughput))
        loss1, sps1 = run_once(args.model, args.cpu, name, args.steps, args.batch)
        loss2, sps2 = run_once(args.model, args.cpu, name, args.steps, args.batch)
        det = "OK " if loss1 == loss2 else "FAIL"
        thr = "OK " if max(sps1, sps2) >= floor else "FAIL"
        print(
            f"{args.model}/{name:28s} loss={loss1:.8f} determinism={det} "
            f"throughput={max(sps1, sps2):9.1f} samples/s/chip floor({floor:.0f})={thr}",
            flush=True,
        )
        if det == "FAIL":
            failures.append(f"{name}: loss {loss1} != {loss2}")
        if thr == "FAIL":
            failures.append(f"{name}: throughput {max(sps1, sps2):.1f} < {floor}")
    if failures:
        print("FAILURES:\n  " + "\n  ".join(failures))
        sys.exit(1)
    print("all benchmark checks passed")


if __name__ == "__main__":
    main()
