#!/usr/bin/env python3
"""Benchmark: MoE transformer training throughput per chip.

The reference CI gates MoE end-to-end but pins only a final loss
(``/root/reference/.buildkite/scripts/benchmark_master.sh:109-144`` — MNIST,
2 local experts/GPU); it publishes no MoE throughput number.  This bench
puts a *measurable* MoE line on the board (VERDICT r3 next #7): a GPT-small
-shaped encoder whose FFNs are top-2 MoE blocks (8 experts, the reference's
2-local-experts-per-GPU density at ep_size=1 on a single chip), bf16
compute, synthetic LM-style data.

Emission protocol: see ``_bench_common`` (JSON lines, last authoritative).
``vs_baseline`` is null — the reference has no MoE throughput floor; the
committed artifact IS the baseline for future rounds.
"""

import os
import time

from _bench_common import BenchHarness

HARNESS = BenchHarness("moe_samples_per_sec_per_chip", "samples/s/chip")

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

# GPT-small-ish MoE encoder: 8 layers x hidden 512, seq 128, 8 experts top-2.
HIDDEN, LAYERS, SEQ, EXPERTS, TOP_K = 512, 8, 128, 8, 2
VOCAB = 8192


def fused_a2a_row(hidden: int, deadline: float):
    """Fused-collective-matmul row: step time of the ep-sharded MoE block
    with the chunked (overlapped) all-to-all schedule vs the monolithic one,
    over every local device.  Emitted as its own JSON line BEFORE the
    authoritative throughput line (last-line protocol); a single-device
    session skips it — there is no all-to-all to overlap."""
    import json as _json

    from jax.sharding import Mesh, PartitionSpec as P

    from bagua_tpu.parallel.moe import MoE

    devs = jax.devices()
    n_dev = len(devs)
    if n_dev < 2 or time.perf_counter() > deadline - 60.0:
        HARNESS.note("fused-a2a row skipped (single device or out of budget)")
        return
    mesh = Mesh(np.array(devs), ("ep",))
    num_experts = n_dev * max(1, EXPERTS // n_dev)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(512 * n_dev, hidden).astype(np.float32))

    def step_ms(chunks):
        moe = MoE(
            hidden_size=hidden, num_experts=num_experts, k=TOP_K,
            capacity_factor=1.25, ep_size=n_dev, ep_axis="ep",
            a2a_chunks=chunks,
        )
        params = moe.init(jax.random.PRNGKey(0), x[: 512])["params"]
        fn = jax.jit(
            jax.shard_map(
                lambda xx: moe.apply({"params": params}, xx)[0],
                mesh=mesh, in_specs=P("ep", None), out_specs=P("ep", None),
                check_vma=False,
            )
        )
        fn(x).block_until_ready()  # compile outside the timed loop
        iters = 10
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(x)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e3

    mono, chunked = step_ms(1), step_ms(4)
    print(_json.dumps({
        "metric": "moe_fused_a2a_step_ms",
        "value": round(chunked, 3),
        "unit": "ms/step (ep-sharded MoE forward)",
        "a2a_chunks": 4,
        "unchunked_ms": round(mono, 3),
        "speedup": round(mono / chunked, 3) if chunked else None,
        "ep_size": n_dev,
        "provisional": True,  # never the authoritative last line
    }), flush=True)


def main():
    import bagua_tpu
    from bagua_tpu.algorithms import build_algorithm
    from bagua_tpu.communication import ALL_AXES
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.parallel.moe import MoE

    deadline = HARNESS.t0 + float(os.environ.get("BENCH_DEADLINE_SEC", "420"))
    HARNESS.note(f"jax ready: {len(jax.devices())} {jax.devices()[0].platform} device(s)")

    group = bagua_tpu.init_process_group()
    n = group.size
    per_chip_batch = int(os.environ.get("BENCH_BATCH_PER_CHIP", "32"))
    hidden = int(os.environ.get("BENCH_MOE_HIDDEN", str(HIDDEN)))
    layers = int(os.environ.get("BENCH_MOE_LAYERS", str(LAYERS)))
    smoke = (per_chip_batch, hidden, layers) != (32, HIDDEN, LAYERS)
    compute_dtype = jnp.bfloat16

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm(dtype=compute_dtype)(x)
            att = nn.SelfAttention(
                num_heads=8, dtype=compute_dtype, deterministic=True
            )(h)
            x = x + att
            h = nn.LayerNorm(dtype=compute_dtype)(x)
            # ep_size=1: all experts local (single-chip bench); the layer is
            # the same one the 8-dev dryrun shards with ep_size=n.
            # expert compute dtype follows the (bf16) activations
            moe_out, l_aux = MoE(
                hidden_size=hidden, num_experts=EXPERTS, k=TOP_K,
                capacity_factor=1.25, ep_size=1, ep_axis=ALL_AXES,
            )(h)
            return x + moe_out, l_aux

    class Model(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(VOCAB, hidden, dtype=compute_dtype)(tokens)
            aux = 0.0
            for _ in range(layers):
                x, l_aux = Block()(x)
                aux = aux + l_aux
            logits = nn.Dense(VOCAB, dtype=compute_dtype)(nn.LayerNorm(dtype=compute_dtype)(x))
            return logits.astype(jnp.float32), aux / layers

    model = Model()

    def loss_fn(params, batch):
        tokens, targets = batch
        logits, l_aux = model.apply({"params": params}, tokens)
        ce = -jnp.mean(
            jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], axis=-1
            )
        )
        return ce + 0.01 * l_aux

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, VOCAB, (per_chip_batch * n, SEQ)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, VOCAB, (per_chip_batch * n, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]
    HARNESS.note("model initialized")

    ddp = DistributedDataParallel(
        loss_fn, optax.adam(1e-3), build_algorithm("gradient_allreduce"),
        process_group=group,
    )
    try:
        state = ddp.init(params)
        for _ in range(2):  # two warmups: fresh-array + steady-state compiles
            state, losses = ddp.train_step(state, (tokens, targets))
            jax.block_until_ready(losses)
        HARNESS.note("compile + warmup done (2 steps)")
        ddp.host_overhead_snapshot(reset=True)  # timed window only
        t0 = time.perf_counter()
        n_iters = 0
        while n_iters < 12 and (n_iters < 2 or time.perf_counter() < deadline):
            state, losses = ddp.train_step(state, (tokens, targets))
            n_iters += 1
        jax.block_until_ready(losses)
        elapsed = time.perf_counter() - t0
        HARNESS.note(f"{n_iters} steps in {elapsed:.2f}s; "
                     f"host overhead {ddp.host_overhead_snapshot()}")
        value = tokens.shape[0] * n_iters / elapsed / n
        extra = {
            "config": f"hidden{hidden} L{layers} seq{SEQ} {EXPERTS}experts top{TOP_K}",
            "vs_baseline": None,
        }
        if smoke:
            extra["config"] = "SMOKE " + extra["config"]
        fused_a2a_row(hidden, deadline)
        HARNESS.emit(value, extra=extra)
    finally:
        ddp.shutdown()


if __name__ == "__main__":
    main()
