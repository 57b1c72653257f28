"""Fused blockwise (flash) attention kernel for ring attention.

The ring-attention loop (``bagua_tpu/parallel/ring_attention.py``) visits one
K/V block per step and folds its contribution into an online-softmax carry.
The expensive part of each visit is the block attention itself: materializing
the ``(b, h, t_q, t_k)`` score matrix in HBM costs more bandwidth than every
other tensor combined.  This module fuses it:

* :func:`block_attention` — jnp reference: returns the block's
  **unnormalized** contribution ``(o, l, m)`` (max-shifted weighted values,
  normalizer, row max).  Carry-free, so the Pallas version needs no awkward
  cross-call carry layouts.
* :func:`block_attention_pallas` — tiled Pallas TPU kernel, grid
  ``(batch x head, t_q/block_q, t_k/block_k)`` with the online-softmax state
  accumulated across the sequential k axis: scores, masking, max, exp and
  both matmuls stay in VMEM at tile granularity, so VMEM use is independent
  of sequence length; only ``(t, d)`` tiles and ``(1, t)`` row-stat vectors
  touch HBM.
* :func:`merge_blocks` — the cheap elementwise online-softmax combine of two
  contributions (XLA fuses it; no kernel needed).

TPU layout choice: scores are computed **transposed** — ``(t_k, t_q)`` via
``dot(k, qᵀ)`` — so the row statistics (max/sum over keys) reduce over the
*sublane* axis and land as ``(1, t_q)`` lane vectors, which Mosaic stores
directly; reducing the minor axis would need an unsupported sublane↔lane
transpose.  Masked entries use a large negative finite (``-1e30``), never
``-inf``, so fully-masked columns stay NaN-free through the merges.

Padding: ``t_q``/``t_k`` pad to their (128-aligned) tile edges, ``d`` to
128; padded keys are masked out, padded queries/channels sliced off after.

Known limit: the mask is a dense ``(b, t_k, t_q)`` int8 array — the one
remaining O(t²) HBM object on this path (64 MiB at t=8,192, where every
query-key tile of every head reads its part of it again; 256 MiB at t=16k;
~16 GiB at 128k).  On the v5e at 20 heads x 8,192 x 256, causal, these
kernels (f32 tiles) took 37.5 ms forward + backward against 24.9 ms for the
Pallas flash kernels that ship with JAX, which make the mask in the kernel
and multiply in bf16 (``PERF.md`` section 6, PR 29; 23.1 ms a layer in the
step after tile tuning), and those against 18.0 ms a layer in the step for
JAX's splash kernels with a fused backward, which take the masked blocks out
of the grid on the host and form each block of scores once (section 6,
PR 30, where the tile edges were decided by the step's time).  Whole-sequence
causal attention goes through ``kernels/causal_attention.py``, which runs
the splash kernels on a TPU.  Compute and gradients are already tile-local,
so the next step for beyond-32k shards is in-kernel mask generation (causal
offsets / segment ids via iota, splash-attention style) replacing the
materialized array.
"""

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.kernels._config import log_decline

NEG = -1e30  # large negative finite (a Python float: Pallas kernels cannot capture traced constants)


# ---------------------------------------------------------------------------
# jnp reference implementation
# ---------------------------------------------------------------------------


def block_attention(
    qf: jnp.ndarray, k_blk: jnp.ndarray, v_blk: jnp.ndarray, mask: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One K/V block's unnormalized attention contribution.

    Args:
        qf: pre-scaled queries ``(b, t_q, h, d)`` float32.
        k_blk, v_blk: the block ``(b, t_k, h, d)`` (any float dtype).
        mask: ``(b, t_q, t_k)`` bool — True = attend (causal x key-padding
            already combined by the caller).

    Returns:
        ``(o, l, m)``: ``o (b, h, t_q, d)`` = sum_k exp(s - m) v (unnormalized),
        ``l (b, h, t_q)`` = sum_k exp(s - m), ``m (b, h, t_q)`` = row max
        (``NEG`` where every key is masked).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
    s = jnp.where(mask[:, None], s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[:, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return o, l, m


def merge_blocks(carry, block):
    """Online-softmax combine of two unnormalized contributions."""
    o, l, m = carry
    o_b, l_b, m_b = block
    m_new = jnp.maximum(m, m_b)
    c = jnp.exp(m - m_new)
    c_b = jnp.exp(m_b - m_new)
    return o * c[..., None] + o_b * c_b[..., None], l * c + l_b * c_b, m_new


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

_LANE = 128
# Default score-tile edge: (BLOCK_K x BLOCK_Q) f32 scores = 1 MB in VMEM,
# with q/k/v/o tiles at d=128 adding ~1.3 MB — comfortably double-buffered
# in a ~16 MB/core arena at any sequence length.
BLOCK_Q = 512
BLOCK_K = 512
# Per-grid-step VMEM budget (v5e arena ~16 MB; headroom for Mosaic's own
# buffers).  Checked against the ACTUAL tile sizes, so callers pushing
# block_q/block_k (or huge head dims) get the graceful jnp fallback, not a
# Mosaic VMEM rejection at runtime.
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _tiles_fit_vmem(bq: int, bk: int, d_p: int) -> bool:
    tiles = (bq * d_p + 2 * 2 * bk * d_p + d_p * bq) * 4  # q + k,v (dbl-buf) + oT
    scores = bk * bq * 4 * 2  # s + p
    mask = 2 * bk * bq + 4 * bk * bq  # int8, double-buffered + its f32 form
    return tiles + scores + mask <= _VMEM_BUDGET_BYTES


def _over_budget(block_q: int, block_k: int, d: int) -> str:
    """The bound a declining flash wrapper names in its log line."""
    return (f"tiles {block_q}x{block_k} at d={d} exceed the "
            f"{_VMEM_BUDGET_BYTES}-byte VMEM budget")


def _tile_edges(tq: int, tk: int, block_q: int, block_k: int):
    """Effective (bq, bk): lane-aligned (128), at most the padded sequence.
    Shared by the VMEM admission check and the kernel launch — they MUST
    agree or an admitted shape could still be rejected by Mosaic."""
    bq = min(block_q, tq + (-tq) % _LANE)
    bq += (-bq) % _LANE
    bk = min(block_k, tk + (-tk) % _LANE)
    bk += (-bk) % _LANE
    return bq, bk


def _resolve_tiles(block_q, block_k):
    """Per-side resolution: explicit arg wins; else the
    ``BAGUA_PALLAS_FLASH_TILES`` env pin ("BQxBK" — how a chip session's
    sweep winner is applied in production); else the default.  A malformed
    env value falls back to the defaults with a warning — an ops knob must
    degrade, not crash every attention call.  Resolved OUTSIDE the jitted
    kernel launch, so the pin takes effect per call (per trace, for in-jit
    callers)."""
    env_q, env_k = None, None
    env = os.environ.get("BAGUA_PALLAS_FLASH_TILES")
    if env:
        try:
            bq_s, _, bk_s = env.partition("x")
            env_q, env_k = int(bq_s), int(bk_s)
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "BAGUA_PALLAS_FLASH_TILES=%r is not 'BQxBK'; using defaults",
                env,
            )
    bq = int(block_q) if block_q is not None else (env_q or BLOCK_Q)
    bk = int(block_k) if block_k is not None else (env_k or BLOCK_K)
    return bq, bk


def flash_block_supported(tq: int, tk: int, d: int,
                          block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    """Whether the tiled kernel handles this shape within its VMEM budget.
    Sequence lengths are unrestricted (the kernel tiles them); the check is
    on one grid step's working set at the effective tile sizes."""
    d_p = d + (-d) % _LANE
    bq, bk = _tile_edges(tq, tk, block_q, block_k)
    return _tiles_fit_vmem(bq, bk, d_p)


def _bwd_tiles_fit_vmem(bq: int, bk: int, d_p: int) -> bool:
    """The backward's working set is larger than the forward's: four
    score-sized temporaries (sT, pT, dpT, dsT) plus q/k/v/do in and a
    dq (or dk+dv) accumulator out."""
    tiles = (2 * bq * d_p + 2 * 2 * bk * d_p + 2 * bq * d_p) * 4  # q,do + k,v(dbl) + out
    scores = bk * bq * 4 * 4  # sT, pT, dpT, dsT
    mask = 2 * bk * bq + 4 * bk * bq  # int8, double-buffered + its f32 form
    return tiles + scores + mask <= _VMEM_BUDGET_BYTES


def flash_bwd_supported(tq: int, tk: int, d: int,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    d_p = d + (-d) % _LANE
    bq, bk = _tile_edges(tq, tk, block_q, block_k)
    return _bwd_tiles_fit_vmem(bq, bk, d_p)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _mask_tile(mask_ref):
    """The int8 mask tile as f32 zeros and ones (via i32: Mosaic has no
    direct i8->f32 cast).  ``tile > 0`` is the select predicate, laid out
    like the f32 tiles it selects between, and ``jnp.max(tile) > 0`` says
    whether any entry is live.  ``jnp.any(mask != 0)`` would say the same,
    but Mosaic (libtpu 0.0.34, on the chip) refuses Pallas's ``reduce_or``
    lowering, a select between two splat constants ("Invalid relayout:
    Non-singleton logical dimension is replicated in destination but not in
    source")."""
    return mask_ref[0].astype(jnp.int32).astype(jnp.float32)


def _tiled_flash_kernel(q_ref, k_ref, v_ref, mask_ref, ot_ref, l_ref, m_ref):
    """One (BLOCK_K, BLOCK_Q) score tile, accumulated across the sequential
    innermost k-grid axis (TPU grids iterate in order, and the output blocks'
    index maps ignore ``ik`` — so ``ot/l/m`` stay VMEM-resident across the
    whole k sweep and carry the online-softmax running state).

    Layout: scores are (t_k, t_q) — queries on lanes — so the row stats are
    (1, t_q) lane vectors, and the output tile is kept TRANSPOSED, ``(d,
    t_q)``: the per-query rescale ``exp(m_prev - m_new)`` is a (1, t_q) lane
    vector that broadcasts over sublanes (d).  Rescaling a (t_q, d) tile
    would need the sublane<->lane transpose Mosaic doesn't do.  The wrapper
    transposes once in HBM at the end.
    """
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        ot_ref[...] = jnp.zeros_like(ot_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)

    mask = _mask_tile(mask_ref)  # (BK, BQ) f32 0/1, transposed layout
    live = mask > 0.0

    # Fully-masked tiles leave the running state untouched (p would be all
    # zeros: m_new == m_prev, c == 1) — skip both MXU matmuls and the exp.
    # Under a causal mask ~half the tiles are dead, so causal long-context
    # forward compute halves with bit-identical results.
    @pl.when(jnp.max(mask) > 0.0)
    def _live_tile():
        q = q_ref[0]  # (BQ, d) f32, pre-scaled
        k = k_ref[0].astype(jnp.float32)  # (BK, d)
        v = v_ref[0].astype(jnp.float32)  # (BK, d)
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BK, BQ)
        s = jnp.where(live, s, NEG)
        m_prev = m_ref[0]  # (1, BQ)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(live, p, 0.0)
        c = jnp.exp(m_prev - m_new)  # (1, BQ) — rescale of the running state
        l_ref[0] = l_ref[0] * c + jnp.sum(p, axis=0, keepdims=True)
        ot_ref[0] = ot_ref[0] * c + jax.lax.dot_general(
            v, p, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (d, BQ): contraction over BK on the MXU
        m_ref[0] = m_new


def block_attention_pallas(
    qf: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool = False,
    block_q: int = None,
    block_k: int = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pallas version of :func:`block_attention` (same contract), tiled:
    grid ``(b*h, t_q/block_q, t_k/block_k)`` with the online-softmax state
    accumulated across the sequential k axis — VMEM use is independent of
    sequence length, so ring-attention shards of any size run fused (the
    old whole-sequence kernel capped out near t=1k and fell back to jnp,
    which materializes the full score matrix in HBM).  Tile sizes resolve
    args -> ``BAGUA_PALLAS_FLASH_TILES`` env pin -> defaults (see
    :func:`_resolve_tiles`).

    Grouped-query attention is native: when ``k_blk``/``v_blk`` carry
    ``h // groups`` heads, the K/V BlockSpecs map each query head's grid
    step to its shared K/V tile (index arithmetic) — no ``jnp.repeat``
    materialization, so K/V HBM traffic stays at the grouped head count.
    """
    block_q, block_k = _resolve_tiles(block_q, block_k)
    b, tq, h, d = qf.shape
    tk = k_blk.shape[1]
    h_kv = k_blk.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads ({h}) must divide by kv heads ({h_kv})")
    if not flash_block_supported(tq, tk, d, block_q, block_k):
        log_decline("block_attention_pallas", (qf.shape, k_blk.shape),
                    _over_budget(block_q, block_k, d))
        g = h // h_kv
        if g > 1:
            k_blk = jnp.repeat(k_blk, g, axis=2)
            v_blk = jnp.repeat(v_blk, g, axis=2)
        return block_attention(qf, k_blk, v_blk, mask)
    return _block_attention_pallas_jit(
        qf, k_blk, v_blk, mask, interpret, block_q, block_k
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_q", "block_k"))
def _block_attention_pallas_jit(qf, k_blk, v_blk, mask, interpret, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, d = qf.shape
    tk = k_blk.shape[1]
    h_kv = k_blk.shape[2]
    g = h // h_kv  # GQA group size (1 = MHA)
    bq, bk = _tile_edges(tq, tk, block_q, block_k)

    # (b, t, heads, d) -> (b*heads, t, d)
    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * x.shape[2], x.shape[1], x.shape[3]
        )

    q3 = _pad_to(_pad_to(to_bh(qf.astype(jnp.float32)), bq, 1), _LANE, 2)
    k3 = _pad_to(_pad_to(to_bh(k_blk), bk, 1), _LANE, 2)
    v3 = _pad_to(_pad_to(to_bh(v_blk), bk, 1), _LANE, 2)
    tq_p, d_p = q3.shape[1], q3.shape[2]
    tk_p = k3.shape[1]

    # mask: (b, t_q, t_k) -> transposed + padded (b, t_k, t_q).  NOT
    # head-expanded: the mask is head-invariant, so the BlockSpec below
    # indexes it with i // h — replicating it to (b*h, ...) in HBM would be
    # an O(h t^2) allocation (128 MiB at h=8, t=4k), re-creating the very
    # HBM traffic the fused kernel removes.  K/V get the same treatment for
    # GQA: grid step i (query head h_i = i % h of batch i // h) reads shared
    # K/V row (i // h) * h_kv + h_i // g.
    mT = jnp.transpose(mask, (0, 2, 1)).astype(jnp.int8)  # (b, t_k, t_q)
    mT = _pad_to(_pad_to(mT, bk, 1), bq, 2)  # padded keys/queries masked off

    def kv_row(i):
        return (i // h) * h_kv + (i % h) // g

    bh = b * h
    ot3, l3, m3 = pl.pallas_call(
        _tiled_flash_kernel,
        grid=(bh, tq_p // bq, tk_p // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d_p), lambda i, iq, ik: (i, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, iq, ik: (kv_row(i), ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, iq, ik: (kv_row(i), ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, bq), lambda i, iq, ik: (i // h, ik, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, d_p, bq), lambda i, iq, ik: (i, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, iq, ik: (i, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, iq, ik: (i, 0, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d_p, tq_p), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq_p), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, tq_p), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3, mT)

    # Undo the kernel's transposed output layout (one HBM pass).
    o = jnp.transpose(ot3, (0, 2, 1))[:, :tq, :d].reshape(b, h, tq, d)
    l = l3[:, 0, :tq].reshape(b, h, tq)
    m = m3[:, 0, :tq].reshape(b, h, tq)
    return o, l, m


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref, do_ref,
                         dq_ref):
    """dq tile, accumulated across the sequential k axis.

    Recomputes the probability tile from (q, k, m) residuals — no O(t^2)
    saved activations.  Same transposed score layout as the forward:
    ``m``/``dl`` are (1, t_q) lane vectors broadcasting over key sublanes.
    """
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    mask = _mask_tile(mask_ref)
    live = mask > 0.0

    @pl.when(jnp.max(mask) > 0.0)  # dead tiles contribute exactly zero
    def _live_tile():
        q = q_ref[0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        sT = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, bq)
        pT = jnp.where(live, jnp.exp(sT - m_ref[0]), 0.0)
        dpT = jax.lax.dot_general(
            v, do_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + dl_ref[0]  # (bk, bq): do.v per (key, query) + the l-path constant
        dsT = pT * dpT
        dq_ref[0] += jax.lax.dot_general(
            dsT, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, d)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, m_ref, dl_ref, do_ref,
                          dk_ref, dv_ref):
    """dk/dv tiles, accumulated across the two sequential innermost grid
    axes: the GQA head group (each shared K/V head collects gradient from
    its ``g`` query heads) and the q axis.  MHA is the ``g == 1`` case."""
    from jax.experimental import pallas as pl

    ig = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(jnp.logical_and(ig == 0, iq == 0))
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    mask = _mask_tile(mask_ref)
    live = mask > 0.0

    @pl.when(jnp.max(mask) > 0.0)  # dead tiles contribute exactly zero
    def _live_tile():
        q = q_ref[0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0]
        sT = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, bq)
        pT = jnp.where(live, jnp.exp(sT - m_ref[0]), 0.0)
        dv_ref[0] += jax.lax.dot_general(
            pT, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, d)
        dpT = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + dl_ref[0]
        dsT = pT * dpT
        dk_ref[0] += jax.lax.dot_general(
            dsT, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, d)


def _jnp_block_vjp(qf, k_blk, v_blk, mask, cot):
    """The exact jnp VJP of :func:`block_attention`, GQA-aware: grouped K/V
    are repeated for the reference math and the resulting gradients are
    summed back over each shared head's query group."""
    b, _, h, d = qf.shape
    tk, h_kv = k_blk.shape[1], k_blk.shape[2]
    g = h // h_kv
    k_r = jnp.repeat(k_blk, g, axis=2) if g > 1 else k_blk
    v_r = jnp.repeat(v_blk, g, axis=2) if g > 1 else v_blk
    _, vjp = jax.vjp(
        lambda a, b_, c: block_attention(a, b_, c, mask), qf, k_r, v_r
    )
    dq, dk, dv = vjp(cot)
    if g > 1:
        dk = dk.reshape(b, tk, h_kv, g, d).sum(axis=3)
        dv = dv.reshape(b, tk, h_kv, g, d).sum(axis=3)
    return dq, dk, dv


def flash_attention_bwd_pallas(
    qf: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    mask: jnp.ndarray,
    m: jnp.ndarray,
    dl: jnp.ndarray,
    do: jnp.ndarray,
    interpret: bool = False,
    block_q: int = None,
    block_k: int = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused flash backward: ``(dq, dk, dv)`` from residuals ``(q, k, v,
    mask, m)`` and cotangents ``(do, dl)`` — probabilities are recomputed
    tile by tile, so backward HBM traffic is O(t·d) like the forward
    instead of the jnp VJP's O(t²) score materialization.

    Semantics: the row-max ``m`` is treated as a CONSTANT (stop-gradient),
    and the ``m`` cotangent is dropped by the caller.  This is exact for
    any consumer whose final function is invariant to the max shift —
    ring/zigzag attention's merge + normalization, this kernel's only user
    — where the dropped terms cancel identically (see
    ``block_attention_fused``).  It is NOT the per-block ``jax.vjp`` of
    :func:`block_attention`, which routes subgradients through argmax.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k = _resolve_tiles(block_q, block_k)
    b, tq, h, d = qf.shape
    tk = k_blk.shape[1]
    h_kv = k_blk.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads ({h}) must divide by kv heads ({h_kv})")
    g = h // h_kv  # GQA group size (1 = MHA)
    if not flash_bwd_supported(tq, tk, d, block_q, block_k):
        # Same graceful-fallback contract as the forward: over-budget tiles
        # get the exact jnp VJP (with the dm cotangent the caller already
        # dropped set to zero), never a Mosaic VMEM rejection mid-training-
        # step.  Exact-vjp and stop-grad-m backwards differ per block but
        # agree on every composed (merge+normalize) gradient — see the
        # block_attention_fused docstring — so mixing them per shape is fine.
        log_decline("flash_attention_bwd_pallas", (qf.shape, k_blk.shape),
                    _over_budget(block_q, block_k, d))
        return _jnp_block_vjp(qf, k_blk, v_blk, mask, (do, dl, jnp.zeros_like(m)))
    bq, bk = _tile_edges(tq, tk, block_q, block_k)

    def to_bh(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * x.shape[2], x.shape[1], x.shape[3]
        )

    q3 = _pad_to(_pad_to(to_bh(qf.astype(jnp.float32)), bq, 1), _LANE, 2)
    k3 = _pad_to(_pad_to(to_bh(k_blk), bk, 1), _LANE, 2)
    v3 = _pad_to(_pad_to(to_bh(v_blk), bk, 1), _LANE, 2)
    do3 = _pad_to(_pad_to(to_bh(do.transpose(0, 2, 1, 3)), bq, 1), _LANE, 2)
    tq_p, d_p = q3.shape[1], q3.shape[2]
    tk_p = k3.shape[1]
    mT = jnp.transpose(mask, (0, 2, 1)).astype(jnp.int8)
    mT = _pad_to(_pad_to(mT, bk, 1), bq, 2)
    # (b, h, tq) -> (bh, 1, tq_p); padded queries are masked, values moot
    m3 = _pad_to(m.reshape(b * h, 1, tq), bq, 2)
    dl3 = _pad_to(dl.reshape(b * h, 1, tq), bq, 2)

    def kv_row(i):
        return (i // h) * h_kv + (i % h) // g

    bh = b * h
    dq3 = pl.pallas_call(
        _flash_bwd_dq_kernel,
        grid=(bh, tq_p // bq, tk_p // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d_p), lambda i, iq, ik: (i, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, iq, ik: (kv_row(i), ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, iq, ik: (kv_row(i), ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, bq), lambda i, iq, ik: (i // h, ik, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, iq, ik: (i, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, iq, ik: (i, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, d_p), lambda i, iq, ik: (i, iq, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, d_p), lambda i, iq, ik: (i, iq, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d_p), jnp.float32),
        interpret=interpret,
    )(q3, k3, v3, mT, m3, dl3, do3)

    # dk/dv: each shared K/V head accumulates over its g query heads (the
    # group axis) and the q tiles — both sequential innermost grid dims, so
    # the output tiles stay VMEM-resident for the whole sweep.  Grid step
    # (i, ik, ig, iq): i indexes (batch x kv head); its query-head row is
    # (i // h_kv) * h + (i % h_kv) * g + ig.
    def q_row(i, ig):
        return (i // h_kv) * h + (i % h_kv) * g + ig

    dk3, dv3 = pl.pallas_call(
        _flash_bwd_dkv_kernel,
        grid=(b * h_kv, tk_p // bk, g, tq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d_p), lambda i, ik, ig, iq: (q_row(i, ig), iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, ik, ig, iq: (i, ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, ik, ig, iq: (i, ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, bq), lambda i, ik, ig, iq: (i // h_kv, ik, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, ik, ig, iq: (q_row(i, ig), 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda i, ik, ig, iq: (q_row(i, ig), 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, d_p), lambda i, ik, ig, iq: (q_row(i, ig), iq, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d_p), lambda i, ik, ig, iq: (i, ik, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d_p), lambda i, ik, ig, iq: (i, ik, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, tk_p, d_p), jnp.float32),
            jax.ShapeDtypeStruct((b * h_kv, tk_p, d_p), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3, mT, m3, dl3, do3)

    def from_bh(x3, t, heads):
        return x3[:, :t, :d].reshape(b, heads, t, d).transpose(0, 2, 1, 3)

    dq = from_bh(dq3, tq, h)  # (b, tq, h, d) — qf's layout
    dk = from_bh(dk3, tk, h_kv).astype(k_blk.dtype)
    dv = from_bh(dv3, tk, h_kv).astype(v_blk.dtype)
    return dq, dk, dv


def block_attention_fused(
    qf: jnp.ndarray,
    k_blk: jnp.ndarray,
    v_blk: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool = False,
    block_q: int = None,
    block_k: int = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Differentiable :func:`block_attention_pallas`: fused Pallas forward,
    jnp-derived backward.

    ``pallas_call`` has no autodiff rule — ``jax.grad`` through the raw
    kernel fails at trace time, which would crash every TRAINING use of
    ring attention the moment the hardware-validation record flips the
    kernel auto-ON.  Two backward paths:

    * **fused** (:func:`flash_attention_bwd_pallas`): tile-recomputed
      probabilities, O(t·d) HBM traffic, stop-gradient-on-``m`` semantics —
      exact for the ring merge + normalization composition (the only
      consumer), where the max-shift terms cancel identically.  Selected by
      ``BAGUA_PALLAS_FLASH_BWD`` / the ``flash_attention_bwd`` record in
      the hardware-validation artifact.
    * **jnp** (default until chip-validated): the exact ``jax.vjp`` of the
      jnp reference — XLA re-materializes the block's O(t²) scores for the
      gradient only; the forward keeps the tiled kernel's profile either
      way."""

    return _block_attention_fused_vjp[(interpret, block_q, block_k)](
        qf, k_blk, v_blk, mask
    )


class _FusedVjpCache(dict):
    """One custom_vjp function per static config.  The mask is an explicit
    primal argument (a closed-over mask would be a TRACER inside jit/
    shard_map traces — 'no constant handler' at lowering) with a ``None``
    cotangent (bool input, tangent type float0)."""

    def __missing__(self, key):
        interpret, block_q, block_k = key

        @jax.custom_vjp
        def f(qf, k_blk, v_blk, mask):
            return block_attention_pallas(
                qf, k_blk, v_blk, mask,
                interpret=interpret, block_q=block_q, block_k=block_k,
            )

        def f_fwd(qf, k_blk, v_blk, mask):
            o, l, m = block_attention_pallas(
                qf, k_blk, v_blk, mask,
                interpret=interpret, block_q=block_q, block_k=block_k,
            )
            return (o, l, m), (qf, k_blk, v_blk, mask, m)

        def f_bwd(res, cot):
            qf, k_blk, v_blk, mask, m = res
            do, dl, _dm = cot  # dm dropped: see the fused-path note above
            from bagua_tpu.kernels._config import resolve_use_pallas

            if resolve_use_pallas(None, "BAGUA_PALLAS_FLASH_BWD",
                                  kernel="flash_attention_bwd"):
                dq, dk, dv = flash_attention_bwd_pallas(
                    qf, k_blk, v_blk, mask, m, dl, do,
                    interpret=interpret, block_q=block_q, block_k=block_k,
                )
                return dq, dk, dv, None
            return (*_jnp_block_vjp(qf, k_blk, v_blk, mask, cot), None)

        f.defvjp(f_fwd, f_bwd)
        self[key] = f
        return f


_block_attention_fused_vjp = _FusedVjpCache()
