"""In-collective blockwise quantization: int8/int4 ring reduce with error
feedback (EQuARX-style, arXiv:2506.17615).

ByteGrad (``algorithms/bytegrad.py``) quantizes *around* the collective —
endpoints compress, but every reduction stage still moves full-precision
partials.  Here the quantization lives *inside* the ring: the travelling
shard crosses every hop as uint8 (int8 per-block min/max) or as two int4
nibbles packed per byte, and each ring step runs one fused
dequantize → add-local → requantize before the next ``ppermute`` send.  Wire
bytes per hop drop ~4x (int8) / ~8x (int4) vs the f32 ring, at one extra
(re)quantization per hop — which is exactly what the per-hop fused kernel
(an extension of PR 2's ``decompress_reduce_requantize``) makes cheap: one
VMEM round-trip per hop on TPU.

Quantization semantics are per *block* (``BAGUA_QR_BLOCK`` elements,
default 4096), reusing the MinMaxUInt8 scheme from
:mod:`bagua_tpu.kernels.minmax_uint8` (and a 16-level variant for int4):

    scale = L / (max - min + 1e-7),  L = 255 (int8) | 15 (int4)

with the same bounded-denominator guard against degenerate blocks
(``minmax_uint8._safe_scale``: near-constant blocks at extreme magnitude
stay finite and round-trip to ~machine precision).  Int4 packs element ``j`` of a block with
element ``j + B/2`` (half-split packing: low nibble = first half, high
nibble = second half) — a layout both jnp and Mosaic vectorize without
strided lane access.

Error feedback: every (re)quantization this rank performs charges its
residual buffer with the *sum-space* error ``s - dequant(quant(s))`` at the
destination shard's region.  Carried in algorithm state and added back into
the next step's gradient, the residual re-enters the average at exactly the
lost magnitude (sum-space error ÷ n = average-space deficit), which is what
keeps the aggressive int4 wire convergent (gated by the loss-parity lane in
``ci/perf_audit.py``).

Three implementations of the per-hop fused op with identical semantics:

* :func:`hop_dequant_add_requant` — pure jnp; the bitwise semantic oracle.
* :func:`hop_dequant_add_requant_pallas` — Pallas TPU kernel, grid over
  block groups, everything in VMEM; falls back to jnp off-tile.
* dispatch via :func:`get_ring_hop` — evidence-gated like every kernel
  family (explicit arg > ``BAGUA_PALLAS_QUANTIZED_RING`` > PALLAS_TPU.json
  record for ``quantized_ring_hop``; always jnp on CPU).
"""

import functools
import os
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.communication import (
    allgather_inplace,
    axis_size,
    ppermute_shift,
    rank_id,
)
from bagua_tpu.kernels._config import log_decline
from bagua_tpu.kernels.minmax_uint8 import (
    LEVELS,
    _requantize_tile,
    _safe_scale,
    _LANE,
    _ROW_ALIGN,
    _pick_block_chunks,
    compress_minmax_uint8,
    decompress_minmax_uint8,
    pallas_chunk_supported,
)
from bagua_tpu.observability.flight_recorder import notify_ring

LEVELS4 = 15.0  # int4: 16 levels
DEFAULT_BLOCK = 4096

#: wire precisions understood by the algorithms/planner ("auto" resolves to
#: a per-bucket choice from this set)
WIRE_PRECISIONS = ("f32", "int8", "int4")

#: f32-bytes-on-the-wire divisor per precision (payload only; the f32
#: (min, max) sidecar adds 8 bytes per block)
PRECISION_DIVISOR = {"int8": 4, "int4": 8}


def resolve_block(requested: Optional[int] = None) -> int:
    """Quantization block size: explicit argument > ``BAGUA_QR_BLOCK`` env
    (read per call, not baked at first trace) > 4096.  Must be even (int4
    half-split packing pairs element ``j`` with ``j + B/2``)."""
    if requested is None:
        env = os.environ.get("BAGUA_QR_BLOCK")
        requested = int(env) if env else DEFAULT_BLOCK
    block = int(requested)
    if block < 2 or block % 2:
        raise ValueError(f"quantized-ring block must be even and >= 2, got {block}")
    return block


# ---------------------------------------------------------------------------
# int4 blockwise compress/decompress (jnp semantic reference)
# ---------------------------------------------------------------------------


def compress_minmax_uint4(blocks: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compress ``blocks`` of shape ``(nblocks, B)`` (B even) to 4-bit levels,
    two nibbles packed per byte: returns ``(packed, minmax)`` with ``packed``
    uint8 of shape ``(nblocks, B // 2)`` and ``minmax`` float32
    ``(nblocks, 2)``.  Element ``j`` rides the low nibble of byte ``j``;
    element ``j + B/2`` rides the high nibble."""
    x = blocks.astype(jnp.float32)
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    # _safe_scale bounds the denominator so near-constant blocks at extreme
    # magnitude can't overflow ``mx * scale`` (same branch-free guard as the
    # uint8 codec).
    scale = _safe_scale(mn, mx, LEVELS4)
    upper = jnp.round(mx * scale)
    lower = upper - LEVELS4
    level = jnp.minimum(jnp.round(x * scale), upper)
    q = level - lower  # (nblocks, B) in [0, 15]
    half = x.shape[1] // 2
    lo = q[:, :half].astype(jnp.int32)
    hi = q[:, half:].astype(jnp.int32)
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return packed, jnp.concatenate([mn, mx], axis=1)


def decompress_minmax_uint4(
    packed: jnp.ndarray, minmax: jnp.ndarray, out_dtype=jnp.float32
) -> jnp.ndarray:
    """Inverse of :func:`compress_minmax_uint4` (lossy): ``(nblocks, B//2)``
    packed bytes back to ``(nblocks, B)`` values."""
    p = packed.astype(jnp.int32)
    q = jnp.concatenate([p & 0xF, p >> 4], axis=1).astype(jnp.float32)
    mn = minmax[:, 0:1]
    mx = minmax[:, 1:2]
    scale = _safe_scale(mn, mx, LEVELS4)
    lower = jnp.round(mx * scale) - LEVELS4
    return ((q + lower) / scale).astype(out_dtype)


def _compressors(bits: int):
    if bits == 8:
        return compress_minmax_uint8, decompress_minmax_uint8
    if bits == 4:
        return compress_minmax_uint4, decompress_minmax_uint4
    raise ValueError(f"quantized ring supports bits in (8, 4), got {bits}")


# ---------------------------------------------------------------------------
# Per-hop fused dequantize → add local partial → requantize
# ---------------------------------------------------------------------------


def hop_dequant_add_requant(
    q: jnp.ndarray, minmax: jnp.ndarray, local: jnp.ndarray, *, bits: int = 8
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One ring step on the travelling shard: dequantize the incoming
    payload, add this rank's local partial, requantize for the next hop.

    ``q`` is the incoming quantized payload (``(nblocks, B)`` uint8 for int8,
    ``(nblocks, B//2)`` packed uint8 for int4), ``minmax`` float32
    ``(nblocks, 2)``, ``local`` float32 ``(nblocks, B)``.  Returns
    ``(q2, minmax2, err)`` where ``err = s - dequant(q2, minmax2)`` is the
    sum-space requantization error this rank absorbs into its error-feedback
    residual.  This jnp composition is the bitwise semantic oracle for the
    Pallas kernel below."""
    comp, deco = _compressors(bits)
    s = deco(q, minmax) + local.astype(jnp.float32)
    q2, mm2 = comp(s)
    return q2, mm2, s - deco(q2, mm2)


def pallas_hop_supported(block: int, bits: int) -> bool:
    """The Pallas hop needs both the unpacked block and (for int4) the packed
    half-block to satisfy the uint8 sublane tiling."""
    if bits == 8:
        return pallas_chunk_supported(block)
    return block % (2 * _LANE * _ROW_ALIGN) == 0


def _hop_block(q, mm, local, levels):
    """One block of one hop: dequantize the f32 levels ``q`` (rows, 128) with
    ``mm`` (1, 2), add ``local``, requantize.  Returns ``(q2, mm2, err)``
    with ``q2`` still f32 levels and ``mm2`` (1, 2)."""
    mn = mm[:, 0:1]
    mx = mm[:, 1:2]
    scale = _safe_scale(mn, mx, levels)
    s = (q + (jnp.round(mx * scale) - levels)) / scale + local
    q2, mm2, lower2, scale2 = _requantize_tile(s, levels)
    return q2, mm2, s - (q2 + lower2) / scale2


def _hop_kernel8(q_ref, mm_ref, loc_ref, qo_ref, mmo_ref, err_ref):
    def block(i, carry):
        q = q_ref[i].astype(jnp.int32).astype(jnp.float32)  # (rows, 128)
        q2, mmo_ref[i], err_ref[i] = _hop_block(q, mm_ref[i], loc_ref[i], LEVELS)
        qo_ref[i] = q2.astype(jnp.int32).astype(jnp.uint8)
        return carry

    jax.lax.fori_loop(0, loc_ref.shape[0], block, 0)


def _hop_kernel4(q_ref, mm_ref, loc_ref, qo_ref, mmo_ref, err_ref):
    def block(i, carry):
        # unpack: low nibble = first half of the block (sublane rows
        # 0..h-1), high nibble = second half — a concat over sublanes, no
        # strided lanes
        p = q_ref[i].astype(jnp.int32)                       # (rows/2, 128)
        q = jnp.concatenate([p & 0xF, p >> 4], axis=0).astype(jnp.float32)
        q2, mmo_ref[i], err_ref[i] = _hop_block(q, mm_ref[i], loc_ref[i], LEVELS4)
        half = q2.shape[0] // 2
        lo = q2[:half].astype(jnp.int32)
        hi = q2[half:].astype(jnp.int32)
        qo_ref[i] = (lo | (hi << 4)).astype(jnp.uint8)
        return carry

    jax.lax.fori_loop(0, loc_ref.shape[0], block, 0)


def hop_dequant_add_requant_pallas(
    q: jnp.ndarray, minmax: jnp.ndarray, local: jnp.ndarray, *,
    bits: int = 8, interpret: bool = False, block_chunks: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas version of :func:`hop_dequant_add_requant`: grid over block
    groups, the incoming payload + local partial + requantized output all
    resident in VMEM for one grid step — the ring's per-hop cost is one VMEM
    round-trip instead of three HBM passes.  Falls back to the jnp oracle
    when the block size doesn't satisfy TPU tiling — semantics identical.

    The int4 hop needs the *packed half-block* on the uint8 tile, i.e. a
    block that is a multiple of 8192: at ``DEFAULT_BLOCK`` (4096) the int4
    hop is therefore ALWAYS the jnp composition, whatever the dispatch
    selected — only ``BAGUA_QR_BLOCK`` / ``block=`` multiples of 8192 reach
    the kernel."""
    nblocks, B = local.shape
    if not pallas_hop_supported(B, bits):
        log_decline(
            f"hop_dequant_add_requant_pallas(bits={bits})", local.shape,
            f"block must be a multiple of "
            f"{(1 if bits == 8 else 2) * _LANE * _ROW_ALIGN} (uint8 tile)",
        )
        return hop_dequant_add_requant(q, minmax, local, bits=bits)
    bc = _pick_block_chunks(nblocks, B, block_chunks)
    return _hop_pallas_jit(q, minmax, local, bits, bc, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "bc", "interpret"))
def _hop_pallas_jit(q, minmax, local, bits: int, bc: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nblocks, B = local.shape
    rows = B // _LANE
    qrows = rows if bits == 8 else rows // 2
    kernel = _hop_kernel8 if bits == 8 else _hop_kernel4
    q2, mm2, err = pl.pallas_call(
        kernel,
        grid=(nblocks // bc,),
        in_specs=[
            pl.BlockSpec((bc, qrows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, 1, 2), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bc, qrows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, 1, 2), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bc, rows, _LANE), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, qrows, _LANE), jnp.uint8),
            jax.ShapeDtypeStruct((nblocks, 1, 2), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, rows, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(
        q.reshape(nblocks, qrows, _LANE),
        minmax.reshape(nblocks, 1, 2),
        local.reshape(nblocks, rows, _LANE),
    )
    qcols = B if bits == 8 else B // 2
    return q2.reshape(nblocks, qcols), mm2.reshape(nblocks, 2), err.reshape(nblocks, B)


def get_ring_hop(bits: int, use_pallas=None, interpret: bool = False) -> Callable:
    """Pick the per-hop fused implementation under the shared evidence-gated
    policy (:func:`bagua_tpu.kernels._config.resolve_use_pallas`): explicit
    argument > ``BAGUA_PALLAS_QUANTIZED_RING`` env pin > PALLAS_TPU.json
    hardware record for ``quantized_ring_hop`` (jnp otherwise, and always on
    CPU backends).  The Pallas entry point still falls back to jnp per call
    for off-tile block sizes."""
    from bagua_tpu.kernels._config import resolve_use_pallas

    if resolve_use_pallas(use_pallas, "BAGUA_PALLAS_QUANTIZED_RING",
                          kernel="quantized_ring_hop"):
        return functools.partial(hop_dequant_add_requant_pallas, bits=bits,
                                 interpret=interpret)
    return functools.partial(hop_dequant_add_requant, bits=bits)


# ---------------------------------------------------------------------------
# The quantized ring collectives (call inside shard_map over group axes)
# ---------------------------------------------------------------------------


def _pad_to_blocks(shard_2d: jnp.ndarray, block: int):
    """(n, S) -> (n, nblocks, B) zero-padded."""
    n, S = shard_2d.shape
    nblocks = -(-S // block)
    pad = nblocks * block - S
    if pad:
        shard_2d = jnp.pad(shard_2d, ((0, 0), (0, pad)))
    return shard_2d.reshape(n, nblocks, block), nblocks


def quantized_ring_reduce_scatter(
    flat: jnp.ndarray, axis=None, *, bits: int = 8, average: bool = True,
    block: Optional[int] = None, hop: Optional[Callable] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise-quantized ring reduce-scatter of a flat f32 array.

    Every rank passes the same-length ``flat`` (length divisible by the ring
    size — the bucket layout's ``align_elems`` guarantees this); rank ``i``
    gets back the reduced shard ``i`` at full precision plus its sum-space
    error-feedback buffer (``flat``-shaped, nonzero only at the shard regions
    whose packages this rank quantized).

    Ring schedule: the package destined for rank ``d`` starts at rank
    ``d + 1`` (which quantizes its local shard ``d``), visits every rank
    forward (``i -> i + 1`` via one ``ppermute`` per step), and each visit
    runs the fused dequantize → add-local → requantize hop — so every hop
    moves compressed bytes (the uint8/packed-int4 payload plus an 8-byte
    f32 min/max sidecar per block).  The final visit (the destination) adds
    its own shard without requantizing: the reduced shard stays f32 on-chip.

    Unrolled Python loop — ``n`` is static, autodiff/scheduler-transparent,
    and arrival order is fixed, so the serial sum order (and therefore every
    payload byte) is deterministic."""
    n = axis_size(axis)
    L = flat.shape[0]
    if L % n:
        raise ValueError(f"flat length {L} not divisible by ring size {n}")
    S = L // n
    x = flat.astype(jnp.float32).reshape(n, S)
    if n == 1:
        return x[0], jnp.zeros_like(flat, jnp.float32)
    B = resolve_block(block)
    comp, deco = _compressors(bits)
    if hop is None:
        hop = get_ring_hop(bits)
    xb, nblocks = _pad_to_blocks(x, B)          # (n, nblocks, B)
    Sp = nblocks * B
    # one flight-recorder descriptor per ring (hop count in-record, not one
    # per hop); fires at trace time, a no-op without an active capture
    notify_ring(
        kind="rs", bits=bits, hops=n - 1,
        wire_bytes=(n - 1) * (Sp // (1 if bits == 8 else 2) + nblocks * 8),
    )
    idx = rank_id(axis)
    tag = f"qr{bits}"
    with jax.named_scope(f"{tag}_quant"):
        d0 = (idx - 1) % n
        local0 = jax.lax.dynamic_index_in_dim(xb, d0, axis=0, keepdims=False)
        q, mm = comp(local0)
        err = jnp.zeros((n, nblocks, B), jnp.float32)
        err = jax.lax.dynamic_update_index_in_dim(
            err, (local0 - deco(q, mm))[None], d0, axis=0
        )
    red = None
    for t in range(1, n):
        with jax.named_scope(f"{tag}_hop{t}"):
            q = ppermute_shift(q, 1, axis)
            mm = ppermute_shift(mm, 1, axis)
            d = (idx - 1 - t) % n
            local = jax.lax.dynamic_index_in_dim(xb, d, axis=0, keepdims=False)
            if t < n - 1:
                q, mm, e = hop(q, mm, local)
                err = jax.lax.dynamic_update_index_in_dim(err, e[None], d, axis=0)
            else:
                # d == idx: the own-destination package arrives; stay f32.
                red = deco(q, mm) + local
    if average:
        red = red / n
    shard = red.reshape(-1)[:S]
    err_flat = err.reshape(n, Sp)[:, :S].reshape(-1)
    return shard, err_flat


def quantized_allgather(
    shard: jnp.ndarray, axis=None, *, bits: int = 8, block: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise-quantized all-gather: every rank compresses its own f32
    shard (one blockwise quantization), the uint8/packed payloads + f32
    min/max sidecars cross the wire, and every rank decompresses all ``n``
    shards.  Returns ``(flat, err)`` with ``flat`` the gathered ``(n * S,)``
    dequantized array (identical on every rank: one quantizer per shard, so
    the wire image is the single source of truth) and ``err`` the owner's
    sum-space quantization error for its shard (feeds error feedback)."""
    n = axis_size(axis)
    S = shard.shape[0]
    if n == 1:
        return shard.astype(jnp.float32), jnp.zeros((S,), jnp.float32)
    B = resolve_block(block)
    comp, deco = _compressors(bits)
    blocks, nblocks = _pad_to_blocks(shard.astype(jnp.float32)[None], B)
    blocks = blocks[0]                           # (nblocks, B)
    # this rank ships its compressed shard to n-1 peers: one descriptor,
    # hop count in-record (trace-time, capture-gated)
    notify_ring(
        kind="ag", bits=bits, hops=n - 1,
        wire_bytes=(n - 1) * (nblocks * B // (1 if bits == 8 else 2) + nblocks * 8),
    )
    tag = f"qr{bits}"
    with jax.named_scope(f"{tag}_ag"):
        q, mm = comp(blocks)
        err = (blocks - deco(q, mm)).reshape(-1)[:S]
        qg = allgather_inplace(q, axis)          # (n, nblocks, B or B//2)
        mmg = allgather_inplace(mm, axis)        # (n, nblocks, 2)
        x = deco(
            qg.reshape(n * nblocks, -1), mmg.reshape(n * nblocks, 2)
        )
        flat = x.reshape(n, nblocks * B)[:, :S].reshape(-1)
    return flat, err


def quantized_ring_allreduce(
    flat: jnp.ndarray, axis=None, *, bits: int = 8, average: bool = True,
    block: Optional[int] = None, hop: Optional[Callable] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantized ring reduce-scatter followed by a quantized all-gather —
    the allreduce the DDP engines run when ``wire_precision`` is int8/int4.

    The reduce-scatter accumulates and the all-gather ships *sums*; the
    average divides once at the very end, so every quantization error lives
    in sum-space and a residual added to the next step's local gradient
    compensates the next average by exactly ``err / n`` — the same deficit
    the average inherited.  Returns ``(out, err)``: the (lossy) reduced
    array, identical on every rank, plus this rank's flat error-feedback
    buffer."""
    n = axis_size(axis)
    if n == 1:
        out = flat.astype(jnp.float32)
        return out, jnp.zeros_like(out)
    shard_sum, err_rs = quantized_ring_reduce_scatter(
        flat, axis, bits=bits, average=False, block=block, hop=hop
    )
    full, err_ag_shard = quantized_allgather(shard_sum, axis, bits=bits, block=block)
    if average:
        full = full / n
    S = shard_sum.shape[0]
    idx = rank_id(axis)
    err = err_rs + jax.lax.dynamic_update_slice(
        jnp.zeros_like(err_rs), err_ag_shard, (idx * S,)
    )
    return full, err


def ring_wire_bytes(numel: int, n: int, bits: int, block: Optional[int] = None) -> int:
    """Exact wire bytes one rank moves for a quantized ring allreduce of
    ``numel`` f32 elements over ``n`` ranks: ``n - 1`` compressed-payload
    hops (reduce-scatter) plus the compressed shard broadcast (all-gather),
    including the f32 min/max sidecars.  The planner's qr legs and the CI
    byte gate both price from this."""
    if bits not in (8, 4):
        raise ValueError(f"ring_wire_bytes prices int8/int4 rings; got bits={bits!r}")
    if n == 1:
        return 0
    B = resolve_block(block)
    S = -(-(numel // n) // B) * B              # padded shard elems
    nblocks = S // B
    payload = S // (1 if bits == 8 else 2)     # bytes per shard payload
    sidecar = nblocks * 8                      # f32 (min, max) per block
    per_hop = payload + sidecar
    # RS: n-1 ppermute sends; AG: this rank ships its shard to n-1 peers
    return (n - 1) * per_hop + (n - 1) * per_hop
