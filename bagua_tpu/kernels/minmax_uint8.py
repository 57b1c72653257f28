"""MinMaxUInt8 quantization: 8-bit lossy compression for collectives.

TPU-native reimplementation of the reference's CUDA MinMaxUInt8 scheme
(``kernels/bagua_kernels.cu:404-572``; pure-torch oracle
``tests/internal/compressor.py:4-33``).  Semantics, per chunk:

    scale       = 255 / (max - min + 1e-7)      (denominator bounded; see
                                                 :func:`_safe_scale`)
    upper_bound = rint(max * scale)
    lower_bound = upper_bound - 255
    q           = clip(rint(x * scale), -inf, upper_bound) - lower_bound   (uint8)
    x'          = (q + lower_bound) / scale

Differences from the reference are layout-only: the CUDA kernel packs min/max
into a 32-byte header ahead of each chunk inside one byte buffer
(``datatypes/mod.rs:703-777`` computes that layout); here the quantized
payload and the per-chunk ``(min, max)`` pairs are separate arrays — XLA
manages buffers, so byte-level packing would only obstruct fusion.

Two implementations with identical semantics:

* :func:`compress_minmax_uint8` — pure jnp; XLA fuses it around collectives.
* :func:`compress_minmax_uint8_pallas` — Pallas TPU kernel, one grid step per
  chunk (used when the chunk fits VMEM; falls back to jnp otherwise).
"""

import functools
import logging
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.kernels._config import log_decline

EPS = 1e-7
LEVELS = 255.0
# Degenerate-range guard terms (see _safe_scale).
REL_EPS = 1e-35
F32_MAX = 3.4028235e38


# ---------------------------------------------------------------------------
# XLA (jnp) implementation — the semantic reference
# ---------------------------------------------------------------------------


def _safe_scale(mn, mx, levels=LEVELS):
    """Per-chunk scale with a bounded denominator.

    The unguarded ``levels / (mx - mn + EPS)`` breaks down twice at the
    extremes: a near-constant chunk at huge magnitude gets a scale so large
    that ``round(mx * scale)`` overflows to inf and ``q`` fills with NaN
    (|mx| >~ 1e29), and a range that itself overflows f32 (``mx - mn`` = inf)
    drives scale to exact zero so decompress divides by it.  Both are cured
    arithmetically — no branch, because a select on the decompress output
    changes how XLA lowers the division per fusion context and breaks the
    cross-engine bitwise wire contract (``tests/test_zero.py``):

    * ``REL_EPS * amax`` bounds ``|mx| * scale`` by ``levels / REL_EPS``
      (~2.6e37 for uint8), keeping the bound representable;
    * the ``F32_MAX`` clamp keeps the denominator finite, so scale > 0.

    For any chunk outside those regimes both terms vanish in f32 rounding
    and the result is bitwise-identical to the unguarded scale."""
    amax = jnp.maximum(jnp.abs(mn), jnp.abs(mx))
    return levels / jnp.minimum(mx - mn + EPS + REL_EPS * amax, F32_MAX)


def _quantize(x, mn, mx):
    scale = _safe_scale(mn, mx)
    upper = jnp.round(mx * scale)
    lower = upper - LEVELS
    level = jnp.minimum(jnp.round(x * scale), upper)
    return (level - lower).astype(jnp.uint8)


def compress_minmax_uint8(chunks: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compress ``chunks`` of shape ``(nchunks, chunk_size)``.

    Returns ``(q, minmax)`` with ``q`` uint8 of the same shape and ``minmax``
    float32 of shape ``(nchunks, 2)``.
    """
    x = chunks.astype(jnp.float32)
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    q = _quantize(x, mn, mx)
    minmax = jnp.concatenate([mn, mx], axis=1)
    return q, minmax


def decompress_minmax_uint8(
    q: jnp.ndarray, minmax: jnp.ndarray, out_dtype=jnp.float32
) -> jnp.ndarray:
    """Inverse of :func:`compress_minmax_uint8` (lossy)."""
    mn = minmax[:, 0:1]
    mx = minmax[:, 1:2]
    scale = _safe_scale(mn, mx)
    lower = jnp.round(mx * scale) - LEVELS
    return ((q.astype(jnp.float32) + lower) / scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------


# TPU tiling: blocks are (sublane, lane)-tiled, so each chunk is viewed as
# (rows, 128) with rows a multiple of 8 (uint8 wants 32).  Chunks that don't
# divide evenly fall back to the jnp implementation — semantics identical.
_LANE = 128
_ROW_ALIGN = 32  # uint8 min sublane tile


_TILING_BOUND = f"chunk must be a multiple of {_LANE * _ROW_ALIGN} (uint8 tile)"


def pallas_chunk_supported(chunk: int) -> bool:
    return chunk % (_LANE * _ROW_ALIGN) == 0


# VMEM budget for one double-buffered grid step (in + out + headroom of the
# ~16 MB/core arena); bounds the chunks-per-step auto-pick.
_VMEM_BLOCK_BYTES = 4 << 20


def _pick_block_chunks(nchunks: int, chunk: int, requested=None) -> int:
    """Chunks per grid step.  More chunks per step amortize grid/pipeline
    overhead (the r4 chip A/B measured the 1-chunk kernel TIED with XLA's
    fused jnp path — 469.0 vs 471.9 samples/s end to end).

    An explicit ``requested`` (argument or ``BAGUA_PALLAS_MINMAX_BLOCK_CHUNKS``,
    read per call — NOT baked at first trace) is honored up to the nearest
    divisor of ``nchunks``, even past the VMEM budget: the validator's sweep
    must really run what its labels say (an over-budget block fails loudly in
    Mosaic and is recorded as such).  Only the auto-pick respects the cap."""
    if requested is None:
        env = os.environ.get("BAGUA_PALLAS_MINMAX_BLOCK_CHUNKS")
        if env:
            try:
                requested = int(env)
            except ValueError:
                # an ops knob must degrade, not crash every compress call
                # (same contract as flash_attention._resolve_tiles)
                logging.getLogger(__name__).warning(
                    "BAGUA_PALLAS_MINMAX_BLOCK_CHUNKS=%r is not an integer; "
                    "using the auto-pick", env,
                )
    if requested is not None:
        bc = max(1, min(int(requested), nchunks))
        while nchunks % bc:
            bc -= 1
        return bc
    cap = max(1, _VMEM_BLOCK_BYTES // (chunk * 4))
    bc = min(cap, 8)
    while nchunks % bc:
        bc -= 1
    return max(1, bc)


def _requantize_tile(x, levels=LEVELS):
    """Min/max-quantize one chunk held as a ``(rows, 128)`` f32 tile, the
    requantize step the compress, fused-reduce and ring-hop kernels share.
    Returns ``(q, mm, lower, scale)``: ``q`` the f32 levels ``0..levels``
    (Mosaic has no direct f32->u8 cast: the caller goes through i32), ``mm``
    the chunk's min and max as ``(1, 2)`` (VMEM refuses scalar stores: one
    vector store), and ``(q + lower) / scale`` the dequantized tile.

    The chunk's min and max stay scalars.  A block-wide form that carries
    them as 1-D ``(bc,)`` vectors aborts the process in Mosaic's layout
    inference (libtpu 0.0.34, on the chip: "Check failed: arr.size() >=
    layout_rank(implicit_dim) (1 vs. 2)")."""
    mn = jnp.min(x)
    mx = jnp.max(x)
    scale = _safe_scale(mn, mx, levels)
    upper = jnp.round(mx * scale)
    lower = upper - levels
    q = jnp.minimum(jnp.round(x * scale), upper) - lower
    return q, jnp.stack([mn, mx]).reshape(1, 2), lower, scale


def _compress_kernel(x_ref, q_ref, mm_ref):
    def chunk(i, carry):
        q, mm_ref[i], _, _ = _requantize_tile(x_ref[i].astype(jnp.float32))
        q_ref[i] = q.astype(jnp.int32).astype(jnp.uint8)
        return carry

    # a loop, not an unrolled block: an explicit block_chunks may ask for
    # many chunks per grid step
    jax.lax.fori_loop(0, x_ref.shape[0], chunk, 0)


def _decompress_kernel(q_ref, mm_ref, x_ref):
    mm = mm_ref[...]                     # (bc, 1, 2)
    mn = mm[:, :, 0:1]                   # (bc, 1, 1)
    mx = mm[:, :, 1:2]
    scale = _safe_scale(mn, mx)
    lower = jnp.round(mx * scale) - LEVELS
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = ((q + lower) / scale).astype(x_ref.dtype)


def compress_minmax_uint8_pallas(
    chunks: jnp.ndarray, interpret: bool = False, block_chunks: int = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas version of :func:`compress_minmax_uint8`: grid over chunk
    blocks, ``block_chunks`` VMEM-resident chunks per step (auto-picked; see
    :func:`_pick_block_chunks` — the validator sweeps explicit values on
    chip).  Falls back to the jnp implementation when the chunk size doesn't
    satisfy TPU tiling.  Block resolution happens OUTSIDE the jit so the env
    pin is honored on every call, not baked at first trace."""
    nchunks, chunk = chunks.shape
    if not pallas_chunk_supported(chunk):
        log_decline("compress_minmax_uint8_pallas", chunks.shape, _TILING_BOUND)
        return compress_minmax_uint8(chunks)
    bc = _pick_block_chunks(nchunks, chunk, block_chunks)
    return _compress_pallas_jit(chunks, interpret, bc)


@functools.partial(jax.jit, static_argnames=("interpret", "bc"))
def _compress_pallas_jit(chunks, interpret: bool, bc: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nchunks, chunk = chunks.shape
    rows = chunk // _LANE
    x3 = chunks.reshape(nchunks, rows, _LANE)
    q, mm = pl.pallas_call(
        _compress_kernel,
        grid=(nchunks // bc,),
        in_specs=[
            pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, 1, 2), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nchunks, rows, _LANE), jnp.uint8),
            jax.ShapeDtypeStruct((nchunks, 1, 2), jnp.float32),
        ],
        interpret=interpret,
    )(x3)
    return q.reshape(nchunks, chunk), mm.reshape(nchunks, 2)


def decompress_minmax_uint8_pallas(
    q: jnp.ndarray, minmax: jnp.ndarray, interpret: bool = False,
    block_chunks: int = None
) -> jnp.ndarray:
    nchunks, chunk = q.shape
    if not pallas_chunk_supported(chunk):
        log_decline("decompress_minmax_uint8_pallas", q.shape, _TILING_BOUND)
        return decompress_minmax_uint8(q, minmax)
    bc = _pick_block_chunks(nchunks, chunk, block_chunks)
    return _decompress_pallas_jit(q, minmax, interpret, bc)


@functools.partial(jax.jit, static_argnames=("interpret", "bc"))
def _decompress_pallas_jit(q, minmax, interpret: bool, bc: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nchunks, chunk = q.shape
    rows = chunk // _LANE
    out = pl.pallas_call(
        _decompress_kernel,
        grid=(nchunks // bc,),
        in_specs=[
            pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, 1, 2), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nchunks, rows, _LANE), jnp.float32),
        interpret=interpret,
    )(q.reshape(nchunks, rows, _LANE), minmax.reshape(nchunks, 1, 2))
    return out.reshape(nchunks, chunk)


# ---------------------------------------------------------------------------
# Fused dequantize → reduce → requantize (ByteGrad's middle three stages)
# ---------------------------------------------------------------------------


def decompress_reduce_requantize(
    q: jnp.ndarray, minmax: jnp.ndarray, average: bool = True
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fuse ByteGrad's middle stages: everyone's received chunk in, the
    reduced + requantized own chunk out.

    ``q`` is uint8 of shape ``(n, chunk)`` (one received chunk per peer),
    ``minmax`` float32 ``(n, 2)``.  Returns ``(q2, mm2)`` with ``q2`` uint8
    ``(1, chunk)`` and ``mm2`` float32 ``(1, 2)`` — exactly
    ``compress(sum(decompress(q, minmax), axis=0[, /n]))``.  This jnp
    composition is the semantic oracle for the Pallas kernel below."""
    x = decompress_minmax_uint8(q, minmax)
    red = jnp.sum(x, axis=0, keepdims=True)
    if average:
        red = red / q.shape[0]
    return compress_minmax_uint8(red)


def _fused_reduce_kernel(q_ref, mm_ref, qo_ref, mmo_ref, *, n, average):
    # dequantize every peer's chunk in place: (n, rows, 128)
    mm = mm_ref[...]                     # (n, 1, 2)
    mn = mm[:, :, 0:1]                   # (n, 1, 1)
    mx = mm[:, :, 1:2]
    scale = _safe_scale(mn, mx)
    lower = jnp.round(mx * scale) - LEVELS
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x = (q + lower) / scale
    # float32 tree-sum over peers, then requantize the reduced chunk — one
    # VMEM round-trip where the staged path pays three HBM passes.
    red = jnp.sum(x, axis=0)             # (rows, 128)
    if average:
        red = red / n                    # division, matching the jnp oracle
    q2, mmo_ref[0], _, _ = _requantize_tile(red)
    qo_ref[0] = q2.astype(jnp.int32).astype(jnp.uint8)


def decompress_reduce_requantize_pallas(
    q: jnp.ndarray, minmax: jnp.ndarray, average: bool = True,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas version of :func:`decompress_reduce_requantize`: the whole
    ``(n, chunk)`` block resident in VMEM for one grid step (the requantize
    needs the reduced chunk's global min/max, so the chunk can't be tiled
    across steps without a cross-step reduction).  Falls back to the jnp
    composition when the chunk doesn't satisfy TPU tiling or the block would
    blow the VMEM budget — semantics identical either way."""
    n, chunk = q.shape
    # resident bytes: u8 in (n*chunk) + f32 dequant (4*n*chunk) + f32 reduced
    # + u8 out (~5*chunk); stay within the double-buffered arena budget
    if not pallas_chunk_supported(chunk):
        log_decline("decompress_reduce_requantize_pallas", q.shape, _TILING_BOUND)
        return decompress_reduce_requantize(q, minmax, average=average)
    if (n + 1) * chunk * 5 > 2 * _VMEM_BLOCK_BYTES:
        log_decline(
            "decompress_reduce_requantize_pallas", q.shape,
            f"(n+1)*chunk*5 = {(n + 1) * chunk * 5} bytes resident > "
            f"{2 * _VMEM_BLOCK_BYTES} VMEM budget",
        )
        return decompress_reduce_requantize(q, minmax, average=average)
    return _fused_reduce_pallas_jit(q, minmax, bool(average), interpret)


@functools.partial(jax.jit, static_argnames=("average", "interpret"))
def _fused_reduce_pallas_jit(q, minmax, average: bool, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, chunk = q.shape
    rows = chunk // _LANE
    q2, mm2 = pl.pallas_call(
        functools.partial(_fused_reduce_kernel, n=n, average=average),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, rows, _LANE), lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, 1, 2), lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, _LANE), lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 2), lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows, _LANE), jnp.uint8),
            jax.ShapeDtypeStruct((1, 1, 2), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(n, rows, _LANE), minmax.reshape(n, 1, 2))
    return q2.reshape(1, chunk), mm2.reshape(1, 2)


def get_fused_reducer(use_pallas=None):
    """Pick the ``decompress_reduce_requantize`` implementation for the
    compressed-allreduce hot loop, under the same evidence-gated policy as
    :func:`get_compressors`: explicit argument > ``BAGUA_PALLAS_FUSED_REDUCE``
    env pin > PALLAS_TPU.json hardware record (jnp otherwise, and always on
    CPU backends).  The Pallas entry point still falls back to jnp per call
    when a chunk doesn't satisfy TPU tiling or VMEM bounds."""
    from bagua_tpu.kernels._config import resolve_use_pallas

    if resolve_use_pallas(use_pallas, "BAGUA_PALLAS_FUSED_REDUCE",
                          kernel="decompress_reduce_requantize"):
        return decompress_reduce_requantize_pallas
    return decompress_reduce_requantize


def get_compressors(use_pallas=None):
    """Pick the (compress, decompress) pair for the bytegrad/low-precision
    hot paths.

    Selection precedence (``kernels._config.resolve_use_pallas``): an
    explicit ``use_pallas`` argument wins; else the env var
    ``BAGUA_PALLAS_COMPRESSION`` (operator kill switch); else auto-selection
    — which requires the ``PALLAS_TPU.json`` hardware-validation record to
    show this kernel Mosaic-compiling, numerics-exact, AND faster than the
    jnp path on a real chip (no record -> jnp).  The Pallas entry points
    themselves still fall back to jnp per-call when a chunk doesn't satisfy
    TPU tiling — so every configuration is semantically identical.
    """
    from bagua_tpu.kernels._config import resolve_use_pallas

    if resolve_use_pallas(use_pallas, "BAGUA_PALLAS_COMPRESSION",
                          kernel="minmax_uint8"):
        return compress_minmax_uint8_pallas, decompress_minmax_uint8_pallas
    return compress_minmax_uint8, decompress_minmax_uint8
