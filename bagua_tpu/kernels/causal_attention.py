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
* on a TPU, the Pallas *splash* kernels that ship with JAX
  (``jax.experimental.pallas.ops.tpu.splash_attention``) over a causal mask:
  the blocks above the diagonal are taken out of the grid on the host, the
  mask is applied only in the blocks the diagonal crosses, and one fused
  backward kernel forms each block's scores and ``dP`` once and gives ``dQ``,
  ``dK`` and ``dV`` (``dQ`` as one partial per ``block_kv_dkv`` keys, summed
  after the kernel).  Chosen by the step's time on the v5e at 20 heads x
  8,192 x 256 (``PERF.md`` section 6, PR 30: JAX's flash kernels, which form
  every block three times and mask every one, and splash's two-kernel
  backward beside it; PR 29: the composition and this repo's own
  ``kernels/flash_attention.py``); not an option.
"""

import functools

import jax
import jax.numpy as jnp

#: query rows per block of the composition: 20 heads x 512 x 8,192 float32
#: scores are 335 MB at the last block, 21 MB at the first
BLOCK_Q = 512
#: tile edges of the splash kernels, decided on the v5e at 20 heads x 8,192 x 256
#: by the step's time among those that fit a kernel's 16 MB of fast memory *in
#: the step* (PERF.md section 6, PR 30).  The forward takes ``block_q`` queries
#: against ``block_kv`` keys a grid step, ``block_kv_compute`` at a time; the
#: fused backward ``block_q_dkv`` against ``block_kv_dkv`` and writes one ``dQ``
#: partial per ``block_kv_dkv`` keys: eight at 8,192 positions, which beat four
#: (2,048 keys fit only with 512 queries)
SPLASH_BLOCKS = dict(
    block_q=1024, block_kv=1024, block_kv_compute=256,
    block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512)
#: the positions must divide by every edge; the edges are powers of two
SPLASH_BLOCK_MAJOR = max(SPLASH_BLOCKS.values())

_NEG = -1e30  # finite: a masked score must not make ``exp(s - m)`` a NaN


def causal_attention(q, k, v, scale: float):
    """``softmax(q k^T * scale + causal mask) v``.  ``q``, ``k``, ``v`` are
    ``(batch, heads, positions, head size)`` with one head size; the result
    has ``q``'s type.  On a TPU the kernels take no scale, so what is computed
    there is ``softmax((q * scale) k^T + causal mask) v`` with ``q * scale``
    rounded to ``q``'s type: the same numbers where ``scale`` is a power of
    two, one more rounding of ``q`` where it is not.  A ``scale`` of 1 says
    that the caller's ``q`` carries the scale already (``models/glm_moe.py``
    multiplies in float32 in the pass that rounds ``q``): ``q`` then goes to
    the kernels as it came, with no pass and no rounding of its own."""
    t = q.shape[2]
    if jax.default_backend() == "tpu" and t % SPLASH_BLOCK_MAJOR == 0:
        return _splash_causal_attention(q, k, v, scale)
    return blocked_causal_attention(q, k, v, scale, min(BLOCK_Q, t))


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, t: int, interpret: bool = False):
    """The kernels of one ``(heads, t)``, built once: the causal mask's block
    tables are made on the host in numpy, and every layer of a model shares
    them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    mask = masks.MultiHeadMask([masks.CausalMask((t, t))] * heads)
    with jax.ensure_compile_time_eval():  # the tables are constants of whatever trace asks first
        return splash.make_splash_mha(
            mask, block_sizes=splash.BlockSizes(use_fused_bwd_kernel=True, **SPLASH_BLOCKS),
            head_shards=1, q_seq_shards=1, interpret=interpret)


def _splash_causal_attention(q, k, v, scale: float, interpret: bool = False):
    kernel = _splash_kernel(q.shape[1], q.shape[2], interpret)
    if scale != 1.0:
        q = (q * scale).astype(q.dtype)
    return jax.vmap(kernel)(q, k, v)


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
