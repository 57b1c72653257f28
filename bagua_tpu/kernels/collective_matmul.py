"""Collective matmul: computation-collective fusion for model-parallel GEMMs.

The data-parallel exchange hides behind the backward pass (backward-anchored
buckets, ZeRO's in-backward reduce-scatter); the *model*-parallel exchanges —
the ``psum`` after :class:`~bagua_tpu.parallel.tensor_parallel.RowParallelDense`
and the all-to-alls around expert compute — sit fully exposed on the critical
path.  This module applies the fused computation-collective decomposition of
"Optimizing Distributed ML Communication with Fused Computation-Collective
Operations" (arXiv:2305.06942) and T3 (arXiv:2401.16677): break the sharded
GEMM into per-rank ring steps so each step's neighbor ``ppermute`` is
independent of that step's tile matmul and XLA's latency-hiding scheduler
overlaps wire with MXU work.  Two primitives:

* :func:`ag_matmul` — **all-gather matmul** (ColumnParallelDense forward on a
  row-sharded input / RowParallelDense backward): multiply the resident
  activation shard while the ring forwards the others, instead of a blocking
  ``all_gather`` followed by one big dot.
* :func:`matmul_rs` — **matmul reduce-scatter** (RowParallelDense forward):
  per ring step compute the partial product destined for one peer and
  accumulate it into the travelling shard, eliminating the trailing ``psum``
  entirely — the ring ``ppermute``s replace the all-reduce.

The ring loops are *unrolled Python loops* (the axis size is static under
``shard_map``), so reverse-mode autodiff works through every step and the
scheduler sees each step's ``collective-permute`` as independent of the next
step's ``dot``.  The per-step tile GEMM is pluggable: the default ``jnp.dot``
composition is the **bitwise oracle**, and :func:`matmul_tile_pallas` swaps in
a Pallas TPU kernel (grid over M×N tiles, K never split, so each output tile
is one whole-K dot — edge tiles are zero-padded externally and sliced off,
which keeps the Pallas path bitwise-identical to the oracle).

Selection follows the ``minmax_uint8`` policy end-to-end
(:func:`get_collective_matmul`): explicit argument > the
``BAGUA_PALLAS_COLLECTIVE_MATMUL`` env switch > the ``PALLAS_TPU.json``
hardware-validation record (``ci/validate_pallas_tpu.py``); jnp on CPU
backends.  Interpret-mode parity runs on the CPU tier
(``tests/test_collective_matmul.py``, ``ci/perf_audit.py --model=tp``).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.kernels._config import log_decline

# TPU tiling: the MXU wants (8, 128)-aligned f32 tiles.  Interpret mode (the
# CPU tier) accepts any tile shape, which is how the edge-tile sweep exercises
# non-divisible M/N without an 8×128 floor.
_LANE = 128
_SUBLANE = 8
_TILE_M = 256
_TILE_N = 256
# VMEM head-room for one double-buffered grid step (x tile + w tile + out).
_VMEM_TILE_BYTES = 8 << 20


def _scope(axis_tag: Optional[str], phase: str):
    """A model-parallel exchange label (or a no-op when untagged)."""
    if axis_tag is None:
        import contextlib

        return contextlib.nullcontext()
    from bagua_tpu.observability.annotations import mp_scope

    return mp_scope(axis_tag, phase)


def _axis_meta(axis_name) -> Tuple[str, int]:
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if len(axes) != 1:
        raise ValueError(
            f"collective matmul rings run over a single mesh axis, got {axes} "
            "(hierarchical multi-axis rings are not supported)"
        )
    return axes[0], jax.lax.axis_size(axes[0])


def _ring_arcs(ring: str, n: int) -> Tuple[int, int]:
    """Hop counts per direction.  ``"uni"`` walks the full ``n - 1``-hop ring
    one way; ``"bidir"`` splits it into two counter-rotating arcs of
    ``ceil((n-1)/2)`` / ``floor((n-1)/2)`` hops — both directions of every
    ICI link carry traffic at once, so the wall-clock hop depth halves."""
    if ring == "uni":
        return n - 1, 0
    if ring == "bidir":
        return -(-(n - 1) // 2), (n - 1) // 2
    raise ValueError(f"ring must be 'uni' or 'bidir', got {ring!r}")


# ---------------------------------------------------------------------------
# Ring primitives (jnp composition = the bitwise oracle)
# ---------------------------------------------------------------------------


def ag_matmul(x_shard, w_local, axis_name, *, dot=None, axis_tag=None,
              ring="uni"):
    """All-gather matmul: ``all_gather(x_shard) @ w_local``, ring-overlapped.

    ``x_shard`` is this rank's ``(m_shard, k)`` row block of the activations,
    ``w_local`` the resident ``(k, n_local)`` weight shard.  Step *t* multiplies
    the currently-held activation block (origin rank ``(idx - t) mod n``) while
    the ring ``ppermute`` forwards it to the next neighbor, so all but the last
    transfer ride under a tile GEMM.  Returns ``(n * m_shard, n_local)`` with
    rows in source-rank order — exactly ``jnp.dot`` of the gathered input.

    ``dot`` is the per-step tile GEMM (default ``jnp.dot`` — the oracle);
    ``axis_tag`` labels the ring's ``ppermute``s for the trace analyzer
    (``bagua_ex/axis=<tag>/phase=ag_ring``).  ``ring="bidir"`` runs two
    counter-rotating arcs so each direction forwards only half the blocks
    (~half the hop depth on a bidirectional torus link); every block is still
    multiplied whole by the same ``dot``, so the output is BITWISE the
    unidirectional ring's.
    """
    dot = dot or jnp.dot
    axis, n = _axis_meta(axis_name)
    kf, kb = _ring_arcs(ring, n)
    if n == 1:
        return dot(x_shard, w_local)
    idx = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    back = [(i, (i - 1) % n) for i in range(n)]
    # parts[t] holds the product of the block from source rank (idx - t) mod n:
    # the forward arc fills t = 1..kf, the backward arc fills n-1 down to n-kb.
    parts = [None] * n
    parts[0] = dot(x_shard, w_local)
    fbuf = bbuf = x_shard
    for t in range(1, kf + 1):
        with _scope(axis_tag, "ag_ring"):
            fbuf = jax.lax.ppermute(fbuf, axis, fwd)
        parts[t] = dot(fbuf, w_local)
        if t <= kb:
            with _scope(axis_tag, "ag_ring"):
                bbuf = jax.lax.ppermute(bbuf, axis, back)
            parts[n - t] = dot(bbuf, w_local)
    # part t came from source rank (idx - t) mod n; reorder so block s of the
    # output is source rank s: out[s] = parts[(idx - s) mod n].
    stacked = jnp.stack(parts)
    stacked = jnp.roll(stacked[::-1], idx + 1, axis=0)
    return stacked.reshape(n * x_shard.shape[0], w_local.shape[-1])


def matmul_rs(x_local, w_local, axis_name, *, dot=None, axis_tag=None,
              ring="uni"):
    """Matmul reduce-scatter: rank ``r``'s row block of ``psum(x @ w)``.

    ``x_local`` is the ``(m, k_local)`` activation with the contraction dim
    sharded, ``w_local`` the ``(k_local, features)`` weight rows.  Instead of
    a full local GEMM followed by a blocking ``psum``, the ring walks the
    destination schedule ``d(r, t) = (r + 1 + t) mod n``: each step computes
    the partial product for one destination's row block and adds it onto the
    accumulator arriving from the right neighbor, so every transfer except the
    last rides under the next tile GEMM and **no all-reduce is emitted at
    all**.  After ``n`` steps rank ``r`` holds rows ``[r*m/n, (r+1)*m/n)`` of
    the fully-summed product (an ``all_gather`` restores the replicated
    layout when the consumer needs it).

    ``ring="bidir"`` splits each destination's accumulation into two
    counter-rotating arcs (sources ``d+1..d+⌈(n-1)/2⌉`` arrive on the
    backward chain, ``d-⌊(n-1)/2⌋..d-1`` on the forward chain) combined at
    the destination — ~half the hop depth, same partial products.  The serial
    sum ORDER differs from the unidirectional walk, so outputs agree to f32
    rounding (bitwise only when the summation is exact, e.g. integer-valued
    operands — how the parity test pins it).

    ``m`` must divide by the ring size; callers with indivisible token counts
    fall back to the ``psum`` path (see ``RowParallelDense``).
    """
    dot = dot or jnp.dot
    axis, n = _axis_meta(axis_name)
    ka, kb = _ring_arcs(ring, n)
    if n == 1:
        return dot(x_local, w_local)
    m = x_local.shape[0]
    if m % n:
        raise ValueError(
            f"matmul_rs needs the leading dim ({m}) to divide by the ring size ({n})"
        )
    idx = jax.lax.axis_index(axis)
    blk = m // n
    fwd = [(i, (i + 1) % n) for i in range(n)]
    back = [(i, (i - 1) % n) for i in range(n)]

    def part(d):
        return dot(
            jax.lax.dynamic_slice_in_dim(x_local, d * blk, blk, axis=0), w_local
        )

    if ring == "uni":
        acc = None
        for t in range(n):
            d = (idx + 1 + t) % n
            if acc is None:
                acc = part(d)
            else:
                with _scope(axis_tag, "rs_ring"):
                    acc = jax.lax.ppermute(acc, axis, back)
                # arrival order is fixed by the ring, so the serial sum order
                # is identical for every dot implementation — bitwise parity
                # holds.
                acc = acc + part(d)
        return acc
    # Backward chain: born at rank d + ka, adds every rank down to (and
    # including) the destination — sources d+ka .. d+1 plus d's own part.
    acc_a = part((idx - ka) % n)
    for t in range(1, ka + 1):
        with _scope(axis_tag, "rs_ring"):
            acc_a = jax.lax.ppermute(acc_a, axis, back)
        acc_a = acc_a + part((idx - ka + t) % n)
    if kb == 0:
        return acc_a
    # Forward chain: born at rank d - kb, adds through d-1, then one last hop
    # delivers it — sources d-kb .. d-1 (the destination's part already rode
    # the backward chain).
    acc_b = part((idx + kb) % n)
    for t in range(1, kb):
        with _scope(axis_tag, "rs_ring"):
            acc_b = jax.lax.ppermute(acc_b, axis, fwd)
        acc_b = acc_b + part((idx + kb - t) % n)
    with _scope(axis_tag, "rs_ring"):
        acc_b = jax.lax.ppermute(acc_b, axis, fwd)
    return acc_a + acc_b


# ---------------------------------------------------------------------------
# Pallas tile GEMM
# ---------------------------------------------------------------------------


def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=o_ref.dtype
    )


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def matmul_tile_pallas(x, w, interpret: bool = False, tile_m: int = None,
                       tile_n: int = None):
    """Tiled Pallas GEMM with bitwise-``jnp.dot`` semantics.

    Grid over (M, N) tiles with K whole per grid step — each output tile is a
    single whole-K dot, so slicing the zero-padded result reproduces
    ``jnp.dot(x, w)`` bit for bit (the contraction order never changes; only
    M/N are partitioned, and a padded row/column influences only padded
    outputs).  Falls back to ``jnp.dot`` when the dtype isn't f32 or a
    whole-K tile would blow the VMEM budget — semantics identical either way.
    """
    m, k = x.shape
    k2, nn = w.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    tm = min(int(tile_m or _TILE_M), _ceil_to(max(m, 1), _SUBLANE))
    tn = min(int(tile_n or _TILE_N), _ceil_to(max(nn, 1), _LANE))
    if not interpret:
        # Mosaic wants (sublane, lane)-aligned blocks; interpret mode (the CPU
        # tier) keeps arbitrary tiles so the edge-tile sweep stays meaningful.
        tm = max(_SUBLANE, (tm // _SUBLANE) * _SUBLANE)
        tn = max(_LANE, (tn // _LANE) * _LANE)
    vmem = 4 * (tm * k + k * tn + tm * tn)
    if x.dtype != jnp.float32 or w.dtype != jnp.float32:
        log_decline("matmul_tile_pallas", (x.shape, w.shape),
                    f"dtypes {x.dtype} x {w.dtype}: the tile GEMM is f32 only")
        return jnp.dot(x, w)
    if vmem > _VMEM_TILE_BYTES:
        log_decline(
            "matmul_tile_pallas", (x.shape, w.shape),
            f"whole-K tile {tm}x{k} + {k}x{tn} + {tm}x{tn} f32 = {vmem} bytes "
            f"> {_VMEM_TILE_BYTES} VMEM budget",
        )
        return jnp.dot(x, w)
    return _tile_matmul(x, w, bool(interpret), tm, tn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _tile_matmul(x, w, interpret, tm, tn):
    return _tile_matmul_jit(x, w, interpret, tm, tn)


def _tile_matmul_fwd(x, w, interpret, tm, tn):
    return _tile_matmul(x, w, interpret, tm, tn), (x, w)


def _tile_matmul_bwd(interpret, tm, tn, res, g):
    # dx = g @ w.T, dw = x.T @ g — both through the same tiled GEMM so the
    # fused layers stay on the Pallas path under autodiff (pallas_call has no
    # automatic transpose rule).
    x, w = res
    dx = matmul_tile_pallas(g, w.T, interpret=interpret)
    dw = matmul_tile_pallas(x.T, g, interpret=interpret)
    return dx, dw


_tile_matmul.defvjp(_tile_matmul_fwd, _tile_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", "tm", "tn"))
def _tile_matmul_jit(x, w, interpret: bool, tm: int, tn: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, nn = w.shape
    mp, np_ = _ceil_to(m, tm), _ceil_to(nn, tn)
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    wp = jnp.pad(w, ((0, 0), (0, np_ - nn))) if np_ != nn else w
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // tm, np_ // tn),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tn), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :nn] if (mp != m or np_ != nn) else out


# ---------------------------------------------------------------------------
# Evidence-gated dispatch
# ---------------------------------------------------------------------------


def get_collective_matmul(use_pallas=None, interpret: bool = False):
    """The ``(ag_matmul, matmul_rs)`` pair with the tile GEMM resolved.

    Selection precedence (``kernels._config.resolve_use_pallas``): an explicit
    ``use_pallas`` wins; else ``BAGUA_PALLAS_COLLECTIVE_MATMUL`` (operator
    kill switch); else the ``PALLAS_TPU.json`` record must show the
    ``collective_matmul`` tile GEMM Mosaic-compiling, bitwise-matching its
    oracle AND beating the jnp dot on a real chip (no record → jnp, and
    always jnp on CPU backends).  The Pallas tile GEMM still falls back to
    ``jnp.dot`` per call outside its dtype/VMEM envelope, so every
    configuration is semantically identical — the ring decomposition (and the
    overlap it buys) is the same either way.
    """
    from bagua_tpu.kernels._config import resolve_use_pallas

    if resolve_use_pallas(use_pallas, "BAGUA_PALLAS_COLLECTIVE_MATMUL",
                          kernel="collective_matmul"):
        dot = functools.partial(matmul_tile_pallas, interpret=interpret)
        return (
            functools.partial(ag_matmul, dot=dot),
            functools.partial(matmul_rs, dot=dot),
        )
    return ag_matmul, matmul_rs
