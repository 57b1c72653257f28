#!/usr/bin/env python3
"""Llama-family pretraining throughput per chip (tokens/s + MFU).

The VGG16/BERT benches mirror the reference's CI workloads; this adds the
LLM-pretraining headline the reference never had (SCALING_PROJECTION's
Llama row has been compute-projected until a chip measurement exists —
``ci/scaling_projection.py`` marks it ``projected_compute``).  Model: a
~550M-param Llama shape (GQA 12q/4kv, head_dim 128 — MXU-native) that fits
one v5e chip with f32 SGD state at seq 1024, batch 4/chip, bf16 compute,
gradient_allreduce DP.

MFU uses the standard 6·N·T estimate, peak 197 bf16 TFLOP/s (v5e);
attention FLOPs are excluded at seq 1024 (negligible) and included as
model FLOPs (x3 fwd+bwd, recompute NOT counted — MFU, not HFU) in the
``BENCH_LLAMA_LONGCTX=1`` mode, where they dominate.  Longctx runs seq
8192 through the fused Pallas attention kernels (forward + flash
backward) under a distinct metric name and artifact.

Emission protocol shared with bench.py (``_bench_common``).  CPU smoke:
``JAX_PLATFORMS=cpu BENCH_LLAMA_SMALL=1 python bench_llama.py``.
"""

import os
import time

from _bench_common import BenchHarness

_LONGCTX = bool(os.environ.get("BENCH_LLAMA_LONGCTX"))
HARNESS = BenchHarness(
    ("llama_longctx_tokens_per_sec_per_chip" if _LONGCTX
     else "llama_tokens_per_sec_per_chip"),
    "tokens/s/chip",
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bagua_tpu.observability.goodput import chip_peak_flops

SEQ = 1024
PER_CHIP_BATCH = 4


def fused_tp_row(cfg, deadline: float):
    """Fused-collective-matmul row: the Llama FFN shape as a tensor-parallel
    Column->Row pair over every local device, ring-fused (matmul_rs, zero
    standalone psum) vs the classic psum path.  Emitted as its own JSON line
    before the authoritative tokens/s line; skipped on a single device (no
    ring) or when the FFN width doesn't divide the device count."""
    import json as _json

    from jax.sharding import Mesh, PartitionSpec as P

    from bagua_tpu.parallel.tensor_parallel import ParallelMLP

    devs = jax.devices()
    tp = len(devs)
    tokens = 1024
    if (tp < 2 or cfg.intermediate_size % tp or tokens % tp
            or time.perf_counter() > deadline - 60.0):
        HARNESS.note("fused-tp row skipped (single device, indivisible width, "
                     "or out of budget)")
        return
    mesh = Mesh(np.array(devs), ("tp",))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(tokens, cfg.hidden_size).astype(np.float32))

    def step_ms(fused):
        mlp = ParallelMLP(
            hidden_features=cfg.intermediate_size, out_features=cfg.hidden_size,
            tp_size=tp, axis_name="tp", fused=fused,
        )
        per_rank = [mlp.init(jax.random.PRNGKey(r), x)["params"] for r in range(tp)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
        fn = jax.jit(
            jax.shard_map(
                lambda p, xx: mlp.apply(
                    {"params": jax.tree.map(lambda q: q[0], p)}, xx
                ),
                mesh=mesh, in_specs=(P("tp"), P()), out_specs=P(),
                check_vma=False,
            )
        )
        fn(stacked, x).block_until_ready()  # compile outside the timed loop
        iters = 10
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(stacked, x)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e3

    psum, ring = step_ms(False), step_ms("auto")
    print(_json.dumps({
        "metric": "llama_fused_tp_ffn_ms",
        "value": round(ring, 3),
        "unit": "ms/step (tp-sharded FFN forward)",
        "psum_path_ms": round(psum, 3),
        "speedup": round(psum / ring, 3) if ring else None,
        "tp_size": tp,
        "ffn": f"{cfg.hidden_size}->{cfg.intermediate_size}->{cfg.hidden_size}",
        "provisional": True,  # never the authoritative last line
    }), flush=True)


def main():
    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.llama import (
        LlamaConfig,
        LlamaModel,
        llama_loss_fn,
        llama_test_config,
    )

    deadline = HARNESS.t0 + float(os.environ.get("BENCH_DEADLINE_SEC", "420"))
    HARNESS.note(f"jax ready: {len(jax.devices())} {jax.devices()[0].platform} device(s)")
    group = bagua_tpu.init_process_group()
    n = group.size

    small = bool(os.environ.get("BENCH_LLAMA_SMALL"))
    longctx = bool(os.environ.get("BENCH_LLAMA_LONGCTX"))
    if small:
        cfg = llama_test_config(compute_dtype=jnp.bfloat16)
        seq, per_chip_batch = 32, 2
    elif longctx:
        # Long-context mode: seq 8192 through the FUSED attention path (the
        # jnp path's 8k^2 score matrices would need ~9 GiB/layer).  sp_axis
        # binds to the DDP mesh axes (size 1 per chip -> the ring
        # degenerates to one fused block over the full local sequence);
        # the kernels are forced on — this bench measures them.  The CPU
        # smoke of this script shrinks the shape and keeps the jnp path
        # (Pallas without interpret has no CPU lowering).
        cpu_smoke = jax.devices()[0].platform == "cpu"
        if not cpu_smoke:
            os.environ["BAGUA_PALLAS_ATTENTION"] = "1"
            os.environ["BAGUA_PALLAS_FLASH_BWD"] = "1"
        seq = 256 if cpu_smoke else 8192
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, num_layers=8, num_heads=8,
            num_kv_heads=4, intermediate_size=2816,
            max_position_embeddings=seq, compute_dtype=jnp.bfloat16,
            sp_axis=("inter", "intra"),
        )
        per_chip_batch = 1
        small = cpu_smoke  # shrunken shapes must not emit chip-grade MFU
    else:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, num_layers=16, num_heads=12,
            num_kv_heads=4, intermediate_size=4096,
            max_position_embeddings=SEQ, compute_dtype=jnp.bfloat16,
        )
        seq, per_chip_batch = SEQ, PER_CHIP_BATCH

    model = LlamaModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
    )["params"]
    n_params = sum(x.size for x in jax.tree.leaves(params))
    HARNESS.note(f"model initialized: {n_params / 1e6:.1f}M params")

    ddp = DistributedDataParallel(
        llama_loss_fn(model), optax.sgd(3e-4, momentum=0.9),
        build_algorithm("gradient_allreduce"), process_group=group,
    )
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    bs = per_chip_batch * n
    # lm_loss_fn's batch is the token ids themselves (next-token targets are
    # the shifted ids, models/gpt.py:135-139)
    batch = jnp.asarray(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))

    def _emit(tokens_per_sec, provisional=False):
        extra = {"vs_baseline": None, "params_m": round(n_params / 1e6, 1)}
        if small:
            extra["config"] = ("SMOKE (longctx config, shrunken seq, jnp path)"
                               if longctx else "SMOKE (test-config shapes)")
        else:
            gqa = f"GQA{cfg.num_heads}q/{cfg.num_kv_heads}kv"
            extra["config"] = (
                f"llama {n_params/1e6:.0f}M {gqa} seq{seq} "
                f"batch{per_chip_batch}/chip gradient_allreduce bf16"
                + (" FUSED-ATTENTION (longctx)" if longctx else "")
            )
            gflop_per_token = 6 * n_params / 1e9
            if longctx:
                # attention dominates at long seq with a small model.
                # MFU convention: model FLOPs only (x3 fwd+bwd, like
                # 6N itself) — the flash backward's recompute is NOT
                # counted (that would be HFU).
                head_dim = cfg.hidden_size // cfg.num_heads
                gflop_per_token += (
                    3 * 4 * seq * head_dim * cfg.num_heads
                    * cfg.num_layers / 2 / 1e9
                )
            # a device_kind outside the peak table (the CPU included) raises
            extra["mfu"] = round(
                tokens_per_sec * gflop_per_token * 1e9 / chip_peak_flops(), 3
            )
        HARNESS.emit(tokens_per_sec, provisional=provisional, extra=extra)

    for i in range(2):  # compile + steady-state executable (see bench.py)
        state, losses = ddp.train_step(state, batch)
        jax.block_until_ready(losses)
    HARNESS.note("compile + warmup done (2 steps)")
    ddp.host_overhead_snapshot(reset=True)  # attribution covers the timed window only

    t0 = time.perf_counter()
    state, losses = ddp.train_step(state, batch)
    jax.block_until_ready(losses)
    _emit(bs * seq / (time.perf_counter() - t0) / n, provisional=True)

    n_iters = 1
    while n_iters < 12 and time.perf_counter() < deadline:
        state, losses = ddp.train_step(state, batch)
        n_iters += 1
    jax.block_until_ready(losses)
    elapsed = time.perf_counter() - t0
    HARNESS.note(f"{n_iters} steps in {elapsed:.2f}s; "
                 f"host overhead {ddp.host_overhead_snapshot()}")
    ddp.shutdown()
    fused_tp_row(cfg, deadline)
    _emit(bs * seq * n_iters / elapsed / n)


if __name__ == "__main__":
    main()
