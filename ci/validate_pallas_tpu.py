#!/usr/bin/env python3
"""Real-chip Pallas kernel validation (VERDICT r2 item 2).

Compiles both Pallas kernels with ``interpret=False`` — i.e. through Mosaic,
onto the actual TPU — checks numerics against the jnp oracle paths, and
micro-benchmarks Pallas vs jnp.  Writes ``PALLAS_TPU.json`` at the repo root
so the validation is a committed artifact.

The kernels under test (reference analog:
``bagua_kernels.cu:404-572`` — the production CUDA MinMaxUInt8 compressors):

* ``compress/decompress_minmax_uint8_pallas`` (``kernels/minmax_uint8.py``)
* ``block_attention_pallas`` (``kernels/flash_attention.py``)
* ``matmul_tile_pallas`` (``kernels/collective_matmul.py`` — the tile GEMM
  the ``ag_matmul``/``matmul_rs`` rings interleave with ``ppermute``)
* ``hop_dequant_add_requant_pallas`` (``kernels/quantized_ring.py`` — the
  fused dequant→add→requant hop of the int8/int4 quantized ring)

If Mosaic rejects a kernel, the failure lands in the JSON (and the kernels'
env kill-switches — ``BAGUA_TPU_PALLAS_MINMAX`` / ``BAGUA_TPU_PALLAS_FLASH``
— are the documented mitigation); the jnp fallback keeps the algorithm tier
correct either way.

Usage: ``python ci/validate_pallas_tpu.py`` on a session where
``jax.default_backend()`` is a TPU.  ``--interpret`` runs the same suite in
interpret mode (CPU CI smoke of this script itself).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # script-path runs don't put the repo root on path
    sys.path.insert(0, REPO)

INTERPRET_SMOKE = False  # set by main() under --interpret


def bench(fn, *args, iters=20):
    if INTERPRET_SMOKE:
        iters = 2  # interpret mode emulates the kernel; timing is meaningless
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def sweep_bench(configs, entry, sweep_key, best_key, time_key, fallback_fn):
    """Bench each ``label -> thunk`` in ``configs``, record the per-config
    sweep, the winner, and its time into ``entry``.  Skipped entirely in
    interpret smoke (every config would clamp to the same emulated kernel
    and the timings are meaningless); the plain ``fallback_fn`` bench is
    used instead.  Config failures (e.g. over-VMEM tiles rejected by
    Mosaic) are recorded by exception name, not raised."""
    if INTERPRET_SMOKE:
        entry[time_key] = round(bench(fallback_fn), 3)
        return
    sweep = {}
    for label, thunk in configs.items():
        try:
            sweep[label] = round(bench(thunk), 3)
        except Exception as e:  # noqa: BLE001
            sweep[label] = f"{type(e).__name__}"
    entry[sweep_key] = sweep
    timed = {k: v for k, v in sweep.items() if isinstance(v, float)}
    if timed:
        best = min(timed, key=timed.get)
        entry[best_key] = best
        entry[time_key] = timed[best]
    else:
        entry[time_key] = round(bench(fallback_fn), 3)


def validate_minmax(interpret, report):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.minmax_uint8 import (
        compress_minmax_uint8,
        compress_minmax_uint8_pallas,
        decompress_minmax_uint8,
        decompress_minmax_uint8_pallas,
    )

    entry = {"kernel": "minmax_uint8"}
    try:
        # 64 MB of gradient data in aligned chunks — the bucket-sized shape
        # the bytegrad tier feeds.  (Interpret-mode smoke shrinks: the
        # emulator is ~1000x slower and only numerics are being checked.)
        nchunks, chunk = (4, 8192) if INTERPRET_SMOKE else (64, 262144)
        x = jnp.asarray(
            np.random.RandomState(0).randn(nchunks, chunk).astype(np.float32)
        )
        q_p, mm_p = compress_minmax_uint8_pallas(x, interpret=interpret)
        q_j, mm_j = compress_minmax_uint8(x)
        jax.block_until_ready((q_p, q_j))
        # Bitwise-identical quantization is the contract the wire needs:
        # every rank must decompress every other rank's bytes identically.
        entry["compress_bitwise_equal"] = bool(jnp.array_equal(q_p, q_j))
        entry["minmax_max_abs_diff"] = float(jnp.max(jnp.abs(mm_p - mm_j)))
        d_p = decompress_minmax_uint8_pallas(q_p, mm_p, interpret=interpret)
        d_j = decompress_minmax_uint8(q_j, mm_j)
        entry["decompress_max_abs_diff"] = float(jnp.max(jnp.abs(d_p - d_j)))
        entry["roundtrip_rel_err"] = float(
            jnp.max(jnp.abs(d_p - x)) / (jnp.max(jnp.abs(x)) + 1e-12)
        )
        # Block-chunks sweep (VERDICT r4 #5: "tune block specs where losing"
        # — the 1-chunk-per-step kernel TIED with jnp on chip).  The winner
        # becomes pallas_compress_ms; per-config times are recorded so the
        # auto-pick default (min(VMEM cap, 8)) can be audited against chip
        # reality, and losers can be pinned off via
        # BAGUA_PALLAS_MINMAX_BLOCK_CHUNKS.
        sweep_bench(
            {
                str(bc): (lambda bc=bc: compress_minmax_uint8_pallas(
                    x, interpret=interpret, block_chunks=bc))
                for bc in (1, 2, 4, 8, 16) if nchunks % bc == 0
            },
            entry, "compress_block_chunks_sweep_ms", "best_block_chunks",
            "pallas_compress_ms",
            lambda: compress_minmax_uint8_pallas(x, interpret=interpret),
        )
        entry["jnp_compress_ms"] = round(bench(compress_minmax_uint8, x), 3)
        # Time decompress at the compress sweep's winning block size — the
        # pair runs with one pinned BAGUA_PALLAS_MINMAX_BLOCK_CHUNKS value
        # in production, so mixed-bc timings would misstate the deployable
        # configuration.
        best_bc = entry.get("best_block_chunks")
        try:
            entry["pallas_decompress_ms"] = round(
                bench(
                    lambda a, b: decompress_minmax_uint8_pallas(
                        a, b, interpret=interpret,
                        block_chunks=int(best_bc) if best_bc else None,
                    ),
                    q_p, mm_p,
                ), 3,
            )
        except Exception as e:  # noqa: BLE001 — a timing-config failure must
            # not masquerade as a kernel-validation failure (numerics passed
            # above); record it and fall back to the auto-picked block size.
            entry["decompress_at_best_bc_error"] = f"{type(e).__name__}"
            entry["pallas_decompress_ms"] = round(
                bench(lambda a, b: decompress_minmax_uint8_pallas(
                    a, b, interpret=interpret), q_p, mm_p), 3,
            )
        entry["jnp_decompress_ms"] = round(bench(decompress_minmax_uint8, q_j, mm_j), 3)
        entry["ok"] = entry["compress_bitwise_equal"] and entry["decompress_max_abs_diff"] < 1e-5
    except Exception as e:  # noqa: BLE001 — Mosaic rejection is a finding, not a crash
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)


def validate_fused_reduce(interpret, report):
    """The fused dequantize→reduce→requantize kernel (ByteGrad's middle
    three stages in one VMEM round-trip).  Bitwise parity with the staged
    jnp composition is the contract: every rank requantizes the same reduced
    chunk, so a single differing byte desyncs the all-gather.  Its record
    gates ``BAGUA_PALLAS_FUSED_REDUCE`` auto-ON via
    ``validated_on_hardware``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.minmax_uint8 import (
        compress_minmax_uint8,
        decompress_reduce_requantize,
        decompress_reduce_requantize_pallas,
    )

    entry = {"kernel": "decompress_reduce_requantize"}
    try:
        # n peers' received chunks for one bucket — the inter-axis fan-in of
        # the hierarchical compressed allreduce (inter=8 on a 4x8 pod shape).
        n, chunk = (4, 8192) if INTERPRET_SMOKE else (8, 262144)
        x = jnp.asarray(
            np.random.RandomState(4).randn(n, chunk).astype(np.float32)
        )
        q, mm = compress_minmax_uint8(x)
        jax.block_until_ready((q, mm))
        q_p, mm_p = decompress_reduce_requantize_pallas(
            q, mm, average=True, interpret=interpret
        )
        q_j, mm_j = decompress_reduce_requantize(q, mm, average=True)
        jax.block_until_ready((q_p, q_j))
        entry["requant_bitwise_equal"] = bool(jnp.array_equal(q_p, q_j))
        entry["minmax_max_abs_diff"] = float(jnp.max(jnp.abs(mm_p - mm_j)))
        s_p = decompress_reduce_requantize_pallas(
            q, mm, average=False, interpret=interpret
        )[0]
        s_j = decompress_reduce_requantize(q, mm, average=False)[0]
        entry["sum_variant_bitwise_equal"] = bool(jnp.array_equal(s_p, s_j))
        entry["pallas_ms"] = round(bench(
            lambda: decompress_reduce_requantize_pallas(
                q, mm, average=True, interpret=interpret)), 3)
        entry["jnp_ms"] = round(bench(
            lambda: decompress_reduce_requantize(q, mm, average=True)), 3)
        entry["ok"] = (
            entry["requant_bitwise_equal"]
            and entry["sum_variant_bitwise_equal"]
            and entry["minmax_max_abs_diff"] < 1e-5
        )
    except Exception as e:  # noqa: BLE001 — Mosaic rejection is a finding, not a crash
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)


def validate_flash(interpret, report):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.flash_attention import block_attention, block_attention_pallas

    entry = {"kernel": "flash_attention_block"}
    try:
        # A real ring-attention shard: 4k tokens per device (the tiled
        # kernel's whole point — the old whole-sequence kernel capped ~1k).
        b, h, tq, tk, d = (1, 2, 256, 256, 128) if INTERPRET_SMOKE else (1, 8, 4096, 4096, 128)
        rs = np.random.RandomState(1)
        # layout contract (flash_attention.py:44-59): (b, t, h, d); mask (b, tq, tk)
        q = jnp.asarray(rs.randn(b, tq, h, d).astype(np.float32)) / np.sqrt(d)
        k = jnp.asarray(rs.randn(b, tk, h, d).astype(np.float32))
        v = jnp.asarray(rs.randn(b, tk, h, d).astype(np.float32))
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((tq, tk), bool)), (b, tq, tk))

        o_p, l_p, m_p = block_attention_pallas(q, k, v, mask, interpret=interpret)
        o_j, l_j, m_j = block_attention(q, k, v, mask)
        jax.block_until_ready((o_p, o_j))
        entry["out_max_abs_diff"] = float(jnp.max(jnp.abs(o_p - o_j)))
        entry["lse_max_abs_diff"] = float(jnp.max(jnp.abs(l_p - l_j)))
        # Tile-size sweep (bq, bk): the winner is recorded as pallas_ms, and
        # applies in production via BAGUA_PALLAS_FLASH_TILES="BQxBK".  Only
        # configs the VMEM guard admits are swept — an over-budget config
        # silently falls back to jnp inside block_attention_pallas, and a
        # jnp time must never masquerade as a Pallas measurement in the
        # auto-ON gate.
        from bagua_tpu.kernels.flash_attention import flash_block_supported

        sweep_bench(
            {
                f"{bq}x{bk}": (lambda bq=bq, bk=bk: block_attention_pallas(
                    q, k, v, mask, interpret=interpret,
                    block_q=bq, block_k=bk))
                for bq, bk in ((256, 256), (512, 512), (512, 1024), (1024, 512))
                if flash_block_supported(tq, tk, d, bq, bk)
            },
            entry, "tile_sweep_ms", "best_tile", "pallas_ms",
            lambda: block_attention_pallas(q, k, v, mask, interpret=interpret),
        )
        entry["jnp_ms"] = round(bench(block_attention, q, k, v, mask), 3)
        entry["ok"] = entry["out_max_abs_diff"] < 2e-2
    except Exception as e:  # noqa: BLE001
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)
    validate_flash_bwd(interpret, report)


def validate_flash_bwd(interpret, report):
    """The fused flash backward: composed-gradient parity with the jnp path
    (normalized attention — the composition where stop-grad-m is exact) and
    an A/B of the two backward implementations.  Its record gates
    ``BAGUA_PALLAS_FLASH_BWD`` auto-ON via ``validated_on_hardware``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.flash_attention import (
        block_attention,
        block_attention_fused,
        flash_attention_bwd_pallas,
    )

    entry = {"kernel": "flash_attention_bwd"}
    try:
        b, h, tq, tk, d = (1, 2, 256, 256, 64) if INTERPRET_SMOKE else (1, 8, 2048, 2048, 128)
        rs = np.random.RandomState(2)
        q = jnp.asarray(rs.randn(b, tq, h, d).astype(np.float32)) / np.sqrt(d)
        k = jnp.asarray(rs.randn(b, tk, h, d).astype(np.float32))
        v = jnp.asarray(rs.randn(b, tk, h, d).astype(np.float32))
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((tq, tk), bool)), (b, tq, tk))

        def normalized(block_fn):
            def f(q, k, v):
                o, l, m = block_fn(q, k, v, mask)
                return jnp.sum(jnp.sin(o / (l[..., None] + 1e-9)))

            return f

        # jnp composed reference gradient
        g_ref = jax.grad(normalized(block_attention), argnums=(0, 1, 2))(q, k, v)
        # fused backward, driven through the same composition
        os.environ["BAGUA_PALLAS_FLASH_BWD"] = "1"
        try:
            fused = lambda a, b_, c, m_: block_attention_fused(  # noqa: E731
                a, b_, c, m_, interpret=interpret)
            g_fused = jax.jit(jax.grad(normalized(
                lambda a, b_, c, m_=mask: fused(a, b_, c, m_)), argnums=(0, 1, 2)
            ))(q, k, v)
        finally:
            os.environ.pop("BAGUA_PALLAS_FLASH_BWD", None)
        entry["grad_max_abs_diff"] = float(max(
            jnp.max(jnp.abs(a - b_)) for a, b_ in zip(g_fused, g_ref)
        ))

        # A/B the backward alone: fused kernels vs the jnp VJP
        o, l, m = block_attention(q, k, v, mask)
        do = jnp.asarray(rs.randn(*o.shape).astype(np.float32))
        dl = jnp.asarray(rs.randn(*l.shape).astype(np.float32))
        entry["pallas_ms"] = round(bench(
            lambda: flash_attention_bwd_pallas(
                q, k, v, mask, m, dl, do, interpret=interpret)), 3)

        # Build the VJP closure ONCE so the timed loop runs the backward
        # alone — jax.vjp evaluates the forward too, and timing that would
        # bias the validated_on_hardware auto-ON gate toward the fused
        # kernel (forward+backward vs backward-only).
        _, jnp_vjp = jax.vjp(
            lambda a, b_, c: block_attention(a, b_, c, mask), q, k, v
        )
        zero_dm = jnp.zeros_like(m)
        entry["jnp_ms"] = round(bench(lambda: jnp_vjp((do, dl, zero_dm))), 3)
        entry["ok"] = entry["grad_max_abs_diff"] < 2e-2
    except Exception as e:  # noqa: BLE001
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)
    validate_long_context(interpret, report)


def validate_long_context(interpret, report):
    """Fused attention fwd+bwd at a 16k-token shard — the regime the tiled
    kernels exist for (the jnp path's 16k^2 f32 scores are ~1 GiB PER
    (batch x head): 8 GiB here, beyond HBM before the backward even
    starts).  Records achieved TFLOPs; no jnp A/B is possible, which is
    itself the finding.  Interpret smoke shrinks the shape (the emulator
    is ~1000x slower) but still executes the full code path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.flash_attention import block_attention_fused

    entry = {"kernel": "flash_attention_long_context"}
    try:
        b, h, t, d = (1, 2, 256, 64) if INTERPRET_SMOKE else (1, 8, 16384, 128)
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(b, t, h, d).astype(np.float32)) / np.sqrt(d)
        k = jnp.asarray(rs.randn(b, t, h, d).astype(np.float32))
        v = jnp.asarray(rs.randn(b, t, h, d).astype(np.float32))
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), (b, t, t))

        # The fused forward runs the pallas kernel unconditionally (gating
        # lives in ring_attention's picker); only the BACKWARD consults the
        # evidence record — force it on, since this run is what CREATES
        # that record (the jnp VJP would OOM on 8 GiB of scores here).
        os.environ["BAGUA_PALLAS_FLASH_BWD"] = "1"
        try:
            def loss(q, k, v):
                o, l, m = block_attention_fused(q, k, v, mask, interpret=interpret)
                return jnp.sum(o / (l[..., None] + 1e-9))

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            grads = grad(q, k, v)
            jax.block_until_ready(grads)
            finite = all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
            entry["grads_finite"] = finite
            entry["fwd_bwd_ms"] = round(bench(lambda: grad(q, k, v), iters=5), 3)
        finally:
            os.environ.pop("BAGUA_PALLAS_FLASH_BWD", None)
        # attention = QK^T + PV: 4 t^2 d FLOPs per (b, h) forward; x3.5 for
        # fwd+bwd (standard flash convention); x1/2 causal.
        gflop = 3.5 * 4 * t * t * d * b * h / 2 / 1e9
        entry["achieved_tflops"] = round(gflop / entry["fwd_bwd_ms"], 1)
        entry["tokens"] = t
        entry["ok"] = finite
        entry["note"] = (
            "no jnp A/B: the unfused path needs ~8 GiB of score matrices "
            "at the chip shape"
        )
    except Exception as e:  # noqa: BLE001
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)


def validate_collective_matmul(interpret, report):
    """The tile GEMM behind ``ag_matmul``/``matmul_rs`` (the ring kernels of
    ``kernels/collective_matmul.py``).  Bitwise parity with ``jnp.dot`` is
    the contract — the ring accumulates partial products across ranks, and
    the pure-jnp oracle composition is what the tests and the perf-audit
    census certify, so the Pallas tile must be a drop-in under it.  Its
    record gates ``BAGUA_PALLAS_COLLECTIVE_MATMUL`` auto-ON via
    ``validated_on_hardware``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.collective_matmul import matmul_tile_pallas

    entry = {"kernel": "collective_matmul"}
    try:
        # One ring step's GEMM at a per-rank TP shard shape (tokens/8 x
        # hidden -> hidden/8): the unit the fused layers issue n times.
        m, k, n = (96, 64, 48) if INTERPRET_SMOKE else (2048, 8192, 1024)
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(m, k).astype(np.float32))
        w = jnp.asarray(rs.randn(k, n).astype(np.float32))
        o_p = matmul_tile_pallas(x, w, interpret=interpret)
        o_j = jnp.dot(x, w, preferred_element_type=jnp.float32)
        jax.block_until_ready((o_p, o_j))
        entry["bitwise_equal"] = bool(jnp.array_equal(o_p, o_j))
        entry["max_abs_diff"] = float(jnp.max(jnp.abs(o_p - o_j)))
        # Edge tiles: shapes that don't divide the tile grid exercise the
        # pad-and-slice path Mosaic actually compiles.
        xe = x[: m - (3 if INTERPRET_SMOKE else 129)]
        we = w[:, : n - (5 if INTERPRET_SMOKE else 65)]
        oe_p = matmul_tile_pallas(xe, we, interpret=interpret)
        oe_j = jnp.dot(xe, we, preferred_element_type=jnp.float32)
        entry["edge_tile_bitwise_equal"] = bool(jnp.array_equal(oe_p, oe_j))
        # Tile sweep: the winner is recorded as pallas_ms (applies in
        # production by passing tile_m/tile_n through the layers' dot).
        sweep_bench(
            {
                f"{tm}x{tn}": (lambda tm=tm, tn=tn: matmul_tile_pallas(
                    x, w, interpret=interpret, tile_m=tm, tile_n=tn))
                for tm, tn in ((256, 256), (512, 256), (256, 512), (512, 512))
            },
            entry, "tile_sweep_ms", "best_tile", "pallas_ms",
            lambda: matmul_tile_pallas(x, w, interpret=interpret),
        )
        entry["jnp_ms"] = round(bench(
            lambda: jnp.dot(x, w, preferred_element_type=jnp.float32)), 3)
        entry["ok"] = (
            entry["bitwise_equal"] and entry["edge_tile_bitwise_equal"]
        )
    except Exception as e:  # noqa: BLE001 — Mosaic rejection is a finding, not a crash
        entry["ok"] = False
        entry["error"] = f"{type(e).__name__}: {e}"[:800]
    report.append(entry)


def validate_quantized_ring_hop(interpret, report):
    """The fused dequantize→add→requantize ring hop behind the quantized
    reduce-scatter (``kernels/quantized_ring.py``).  Bitwise parity on the
    requantized payload AND the sum-space error is the contract: the payload
    travels the ring (a differing byte desyncs every downstream hop) and the
    error feeds the per-bucket error-feedback residual.  Its record gates
    ``BAGUA_PALLAS_QUANTIZED_RING`` auto-ON via ``validated_on_hardware``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.kernels.quantized_ring import (
        _compressors,
        hop_dequant_add_requant,
        hop_dequant_add_requant_pallas,
    )

    for bits, block in ((8, 4096), (4, 8192)):
        entry = {"kernel": f"quantized_ring_hop_int{bits}"}
        try:
            # One travelling shard at a bucket-sized shape (the unit the ring
            # runs n-1 times per bucket).
            nblocks = 4 if INTERPRET_SMOKE else 4096
            rs = np.random.RandomState(6 + bits)
            comp, _ = _compressors(bits)
            incoming = jnp.asarray(rs.randn(nblocks, block).astype(np.float32))
            local = jnp.asarray(rs.randn(nblocks, block).astype(np.float32))
            q, mm = comp(incoming)
            jax.block_until_ready((q, mm))
            q_p, mm_p, err_p = hop_dequant_add_requant_pallas(
                q, mm, local, bits=bits, interpret=interpret
            )
            q_j, mm_j, err_j = hop_dequant_add_requant(q, mm, local, bits=bits)
            jax.block_until_ready((q_p, q_j))
            entry["payload_bitwise_equal"] = bool(jnp.array_equal(q_p, q_j))
            entry["err_bitwise_equal"] = bool(jnp.array_equal(err_p, err_j))
            entry["minmax_max_abs_diff"] = float(jnp.max(jnp.abs(mm_p - mm_j)))
            entry["pallas_ms"] = round(bench(
                lambda: hop_dequant_add_requant_pallas(
                    q, mm, local, bits=bits, interpret=interpret)), 3)
            entry["jnp_ms"] = round(bench(
                lambda: hop_dequant_add_requant(q, mm, local, bits=bits)), 3)
            entry["ok"] = (
                entry["payload_bitwise_equal"]
                and entry["err_bitwise_equal"]
                and entry["minmax_max_abs_diff"] < 1e-5
            )
        except Exception as e:  # noqa: BLE001 — Mosaic rejection is a finding, not a crash
            entry["ok"] = False
            entry["error"] = f"{type(e).__name__}: {e}"[:800]
        report.append(entry)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true",
                    help="interpret-mode smoke of this script (CPU CI)")
    ap.add_argument("--out", default=os.path.join(REPO, "PALLAS_TPU.json"))
    args = ap.parse_args()
    import jax

    if args.interpret:
        global INTERPRET_SMOKE
        INTERPRET_SMOKE = True

    backend = jax.default_backend()
    if backend == "cpu" and not args.interpret:
        print("refusing: backend is cpu and --interpret not set", file=sys.stderr)
        sys.exit(2)

    report = []
    validate_minmax(args.interpret, report)
    validate_fused_reduce(args.interpret, report)
    validate_flash(args.interpret, report)
    validate_collective_matmul(args.interpret, report)
    validate_quantized_ring_hop(args.interpret, report)

    result = {
        "backend": backend,
        "device": str(jax.devices()[0]),
        "interpret": args.interpret,
        "kernels": report,
        "all_ok": all(e["ok"] for e in report),
        "notes": {
            "collective_matmul": (
                "awaiting chip evidence: interpret-mode timings (pallas_ms vs"
                " jnp_ms) measure the CPU emulator, not the Mosaic ring —"
                " dispatch stays jnp until a backend=tpu non-interpret run"
                " lands here"
            ),
            "perflab_basis": (
                "bagua_tpu.perflab marks cells whose wire program rides"
                " Pallas-gated kernels as basis=modeled-jnp-fallback until"
                " this artifact carries backend=tpu, interpret=false evidence"
                " for every gated kernel (see docs/perflab.md)"
            ),
        },
    }
    # Artifact first, stdout second: a closed pipe or session cap must not
    # cost the measurement.
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))
    sys.exit(0 if result["all_ok"] else 1)


if __name__ == "__main__":
    main()
