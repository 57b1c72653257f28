"""Causal self-attention that never holds more than a block of scores, in the
forward and in the backward pass.

``softmax(q k^T * scale + causal) v`` over a whole sequence at 8,192
positions and 20 heads is 5.4 GB of float32 scores a layer if written down
(``ring_attention._block_attention_local``, the test oracle, writes them).
Two implementations of the same function, one per backend:

* :func:`blocked_causal_attention`, a composition in ``jax.numpy``: the
  queries are taken ``block_q`` at a time against the keys at or before them
  (the blocks above the diagonal are never formed), the forward keeps only the
  output and each row's log-sum-exp, and the hand-written backward builds each
  block's probabilities again from them.  It runs anywhere and is what every
  backend but the TPU runs.
* on a TPU, the Pallas flash kernels that ship with JAX
  (``jax.experimental.pallas.ops.tpu.flash_attention``: forward, dq and dk/dv
  kernels, causal blocks skipped inside the kernel).  Chosen once, by the
  step's time on the v5e at 20 heads x 8,192 x 256 (``PERF.md`` section 6,
  PR 29: the composition and this repo's own ``kernels/flash_attention.py``
  beside it); not an option.
"""

import functools

import jax
import jax.numpy as jnp

#: query rows per block of the composition: 20 heads x 512 x 8,192 float32
#: scores are 335 MB at the last block, 21 MB at the first
BLOCK_Q = 512
#: tile edges of the Pallas kernels on the chip, each kernel's measured at
#: 20 heads x 8,192 x 256 on the v5e (PERF.md section 6, PR 29): 512 everywhere
#: but the forward's 1,024 queries against 1,024 keys a major step, the dk/dv
#: kernel's 1,024 keys a major step and the dq kernel's 1,024 queries
FLASH_BLOCK = 512
FLASH_BLOCK_MAJOR = 1024

_NEG = -1e30  # finite: a masked score must not make ``exp(s - m)`` a NaN


def causal_attention(q, k, v, scale: float):
    """``softmax(q k^T * scale + causal mask) v``.  ``q``, ``k``, ``v`` are
    ``(batch, heads, positions, head size)`` with one head size; the result
    has ``q``'s type."""
    t = q.shape[2]
    if jax.default_backend() == "tpu" and t % FLASH_BLOCK_MAJOR == 0:
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        n, major = FLASH_BLOCK, FLASH_BLOCK_MAJOR
        blocks = fa.BlockSizes(
            block_q=major, block_k_major=major, block_k=n, block_b=1,
            block_q_major_dkv=n, block_k_major_dkv=major, block_k_dkv=n, block_q_dkv=n,
            block_k_major_dq=n, block_k_dq=n, block_q_dq=major)
        return fa.flash_attention(q, k, v, causal=True, sm_scale=scale, block_sizes=blocks)
    return blocked_causal_attention(q, k, v, scale, min(BLOCK_Q, t))


def _blocks(t: int, block_q: int):
    if t % block_q:
        raise ValueError(f"{t} positions do not divide into query blocks of {block_q}")
    return [(i * block_q, (i + 1) * block_q) for i in range(t // block_q)]


def _block_scores(q_blk, k_seen, start: int, scale: float):
    """Float32 scores of query rows ``start ..`` against keys ``0 .. end``,
    masked above the diagonal."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_seen,
                   preferred_element_type=jnp.float32) * scale
    rows = start + jnp.arange(q_blk.shape[2])[:, None]
    return jnp.where(jnp.arange(k_seen.shape[2])[None, :] <= rows, s, _NEG)


def _forward(q, k, v, scale, block_q):
    outs, lses = [], []
    for start, end in _blocks(q.shape[2], block_q):
        s = _block_scores(q[:, :, start:end], k[:, :, :end], start, scale)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :end],
                       preferred_element_type=jnp.float32) / l
        outs.append(o.astype(q.dtype))
        lses.append((m + jnp.log(l))[..., 0])
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blocked_causal_attention(q, k, v, scale: float, block_q: int):
    return _forward(q, k, v, scale, block_q)[0]


def _fwd(q, k, v, scale, block_q):
    out, lse = _forward(q, k, v, scale, block_q)
    return out, (q, k, v, out, lse)


def _bwd(scale, block_q, res, d_out):
    q, k, v, out, lse = res
    f32 = jnp.float32
    # softmax's backward needs each row's sum of p * dp, which is do . o
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1, keepdims=True)
    dq = []
    dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    for start, end in _blocks(q.shape[2], block_q):
        q_blk, do_blk = q[:, :, start:end], d_out[:, :, start:end]
        s = _block_scores(q_blk, k[:, :, :end], start, scale)
        p = jnp.exp(s - lse[:, :, start:end, None])
        dv = dv.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(v.dtype), do_blk, preferred_element_type=f32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, v[:, :, :end], preferred_element_type=f32)
        ds = (p * (dp - delta[:, :, start:end]) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhqk,bhkd->bhqd", ds, k[:, :, :end],
                             preferred_element_type=f32).astype(q.dtype))
        dk = dk.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_blk, preferred_element_type=f32))
    return jnp.concatenate(dq, axis=2), dk.astype(k.dtype), dv.astype(v.dtype)


blocked_causal_attention.defvjp(_fwd, _bwd)
