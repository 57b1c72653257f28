#!/usr/bin/env python3
"""Secondary benchmark: BERT-Large MLM training throughput per chip
(the reference's second headline workload, ``README.md:50-53``; ByteGrad
config from BASELINE.json).

Emission protocol shared with bench.py (see ``_bench_common``).  Also
compares the ByteGrad compression hot path with the Pallas TPU kernels vs
the fused-jnp implementation and reports which one actually runs faster.
"""

import os
import time

from _bench_common import BenchHarness

HARNESS = BenchHarness("bert_large_mlm_samples_per_sec_per_chip", "samples/s/chip")

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bagua_tpu.observability.goodput import chip_peak_flops

# BERT-Large ~334M params incl. MLM head; fwd+bwd ~= 6 * params FLOPs/token.
TRAIN_GFLOP_PER_SAMPLE = 6 * 334e6 * 128 / 1e9


def _emit(sps, provisional=False, extra=None):
    extra = dict(extra or {})
    extra.setdefault("vs_baseline", None)
    small = bool(os.environ.get("BENCH_BERT_SMALL"))
    extra["config"] = (
        "SMOKE bert-mini seq64 batch4/chip bytegrad bf16"
        if small
        else "seq128 batch32/chip bytegrad bf16"
    )
    if not small:
        # TRAIN_GFLOP_PER_SAMPLE is the BERT-Large seq128 constant; an MFU
        # computed from it in smoke mode would be wildly overstated.  A
        # device_kind outside the peak table (the CPU included) raises.
        extra["mfu"] = round(sps * TRAIN_GFLOP_PER_SAMPLE * 1e9 / chip_peak_flops(), 3)
    HARNESS.emit(sps, provisional=provisional, extra=extra)


def run(use_pallas, n_iters):
    import bagua_tpu
    from bagua_tpu.algorithms import Algorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.bert import BertForPreTraining, bert_large_config, mlm_loss_fn

    group = bagua_tpu.get_default_group()
    n = group.size
    seq, per_chip_batch = 128, 32

    if os.environ.get("BENCH_BERT_SMALL"):
        # Smoke of the script itself (run with JAX_PLATFORMS=cpu); the
        # measured config is BERT-Large.
        from bagua_tpu.models.bert import BertConfig

        seq, per_chip_batch = 64, 4
        cfg = BertConfig(
            vocab_size=1000, hidden_size=128, num_layers=2, num_heads=4,
            intermediate_size=256, max_position_embeddings=seq,
            compute_dtype=jnp.bfloat16,
        )
    else:
        cfg = bert_large_config(compute_dtype=jnp.bfloat16, max_position_embeddings=seq)
    model = BertForPreTraining(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, seq), jnp.int32))["params"]
    ddp = DistributedDataParallel(
        mlm_loss_fn(model), optax.sgd(1e-3),
        Algorithm.init("bytegrad", use_pallas=use_pallas), process_group=group,
    )
    try:
        state = ddp.init(params)

        rng = np.random.RandomState(0)
        bs = per_chip_batch * n
        x = jnp.asarray(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))
        y = jnp.asarray(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int32))

        state, losses = ddp.train_step(state, (x, y))
        jax.block_until_ready(losses)
        HARNESS.note(f"compile + warmup done (pallas={use_pallas})")
        ddp.host_overhead_snapshot(reset=True)  # timed window only

        t0 = time.perf_counter()
        state, losses = ddp.train_step(state, (x, y))
        jax.block_until_ready(losses)
        first = bs / (time.perf_counter() - t0) / n

        t0 = time.perf_counter()
        for _ in range(n_iters):
            state, losses = ddp.train_step(state, (x, y))
        jax.block_until_ready(losses)
        sps = bs * n_iters / (time.perf_counter() - t0) / n
        HARNESS.note(f"pallas={use_pallas}: host overhead {ddp.host_overhead_snapshot()}")
    finally:
        ddp.shutdown()
    return first, sps


def main():
    import bagua_tpu

    HARNESS.note(f"jax ready: {len(jax.devices())} {jax.devices()[0].platform} device(s)")
    bagua_tpu.init_process_group()
    on_tpu = jax.devices()[0].platform != "cpu"

    first, sps_jnp = run(use_pallas=False, n_iters=10)
    # provisional = the measured window (never the noisy single-step timing:
    # it may stand as the final line if the pallas pass hangs)
    _emit(sps_jnp, provisional=True, extra={"compressor": "jnp"})
    HARNESS.note(f"jnp compressor: {sps_jnp:.1f} samples/s/chip")

    sps_pallas = None
    if on_tpu:
        _, sps_pallas = run(use_pallas=True, n_iters=10)
        HARNESS.note(f"pallas compressor: {sps_pallas:.1f} samples/s/chip")

    best = max(sps_jnp, sps_pallas or 0.0)
    _emit(
        best,
        extra={
            "compressor": "pallas" if sps_pallas and sps_pallas >= sps_jnp else "jnp",
            "samples_per_sec_jnp": round(sps_jnp, 2),
            "samples_per_sec_pallas": round(sps_pallas, 2) if sps_pallas else None,
        },
    )


if __name__ == "__main__":
    main()
