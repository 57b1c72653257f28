"""Shared numpy oracles for MinMaxUInt8 compression (reference semantics:
``tests/internal/compressor.py:4-33`` / ``bagua_kernels.cu:404-480``)."""

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-7
# Degenerate-range guard terms — mirror bagua_tpu.kernels.minmax_uint8.
REL_EPS = 1e-35
F32_MAX = 3.4028235e38


def oracle_scale(mn, mx, levels=255.0):
    """Bounded-denominator scale (mirrors ``minmax_uint8._safe_scale``):
    the relative term keeps ``rint(mx * scale)`` representable for
    near-constant chunks at extreme magnitude, the clamp keeps scale > 0
    when the range itself overflows f32; both vanish in f32 rounding for
    any sane chunk."""
    amax = np.maximum(np.abs(mn), np.abs(mx))
    return np.float32(levels) / np.minimum(
        mx - mn + np.float32(EPS) + np.float32(REL_EPS) * amax,
        np.float32(F32_MAX),
    )


def oracle_compress(chunks: np.ndarray):
    mn = chunks.min(axis=1, keepdims=True)
    mx = chunks.max(axis=1, keepdims=True)
    scale = oracle_scale(mn, mx)
    upper = np.rint(mx * scale)
    lower = upper - 255.0
    q = np.minimum(np.rint(chunks * scale), upper) - lower
    return q.astype(np.uint8), np.concatenate([mn, mx], axis=1)


def oracle_decompress(q: np.ndarray, minmax: np.ndarray):
    mn, mx = minmax[:, 0:1], minmax[:, 1:2]
    scale = oracle_scale(mn, mx)
    lower = np.rint(mx * scale) - 255.0
    return ((q.astype(np.float32) + lower) / scale).astype(np.float32)


def oracle_compressed_allreduce(per_rank: np.ndarray, average: bool = True):
    """Numpy simulation of compress→a2a→decompress→reduce→compress→allgather."""
    n, numel = per_rank.shape
    chunk = numel // n
    qs, mms = [], []
    for r in range(n):
        q, mm = oracle_compress(per_rank[r].reshape(n, chunk))
        qs.append(q)
        mms.append(mm)
    reduced = []
    for r in range(n):
        acc = np.zeros((chunk,), np.float32)
        for s in range(n):
            acc += oracle_decompress(qs[s][r : r + 1], mms[s][r : r + 1])[0]
        if average:
            acc /= n
        reduced.append(acc)
    out = []
    for r in range(n):
        q, mm = oracle_compress(reduced[r][None])
        out.append(oracle_decompress(q, mm)[0])
    return np.concatenate(out)


# -- oracles of the decoder models' shared parts ------------------------------
# (``tests/test_causal_attention.py``, ``tests/test_decoder.py`` and the
# model files' tests)


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def quadratic_attention(q, k, v, scale, window=None):
    """Every score written down under the explicit mask (causal, and a window
    of so many keys that counts the current position), each key-value head
    repeated for its group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(q.shape[2])[None, :]
    seen = (i >= j) if window is None else (i >= j) & (i - j < window)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)


def both_passes(attn, q, k, v, d_out):
    """``(out, dq, dk, dv)`` of ``attn(q, k, v)`` under the cotangent ``d_out``."""
    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(d_out.astype(out.dtype))
