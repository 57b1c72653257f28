"""Causal self-attention, over all earlier keys or over a window of them,
that never holds more than a block of scores, in the forward and in the
backward pass.

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

Grouped queries: ``k`` and ``v`` may have fewer heads than ``q``, a divisor
of its count; key-value head ``n`` then serves the query heads ``n * g ..
(n + 1) * g - 1``.  The composition lays a group's query heads along the
rows of one block of scores against their key-value head.  On a TPU each
key-value head and its group go to splash's multi-query kernels
(``make_splash_mqa``), which read one ``k`` and one ``v`` for all of a
group's query heads and add ``dK`` and ``dV`` up over the group inside the
backward kernel: nothing is repeated on either backend.  By the step's time
on the v5e at 32 query heads on 8 key-value heads x 8,192 x 64 (``PERF.md``
section 6, PR 33): 157.98 ms against 160.59 for the multi-head kernels
indexing the key-value head by ``query head // g`` and 160.25 for keys
repeated four times; the tile edges decided at head size 256 measured the
same as five others at head size 64 (160.54 to 161.33) and serve both.  With
as many key-value heads as query heads (``models/glm_moe.py``) both backends
run what they ran.

One mask, two shapes.  With ``window`` position ``i`` sees the keys ``j`` with
``i - window < j <= i`` (the window counts the current position), and each
backend leaves the blocks behind the window out as it leaves the blocks
above the diagonal out: the composition takes a block's keys from ``start -
window + 1`` and bounds the scores from below as from above; on a TPU the
mask is splash's ``LocalMask`` with ``window - 1`` keys to the left and none
to the right, whose blocks behind the window leave the grid on the host and
whose two edges are applied only in the blocks they cross.  At 8,192
positions and a window of 4,096 that is 30 of a layer's 36 tiles of 1,024 x
1,024 (``models/smallthinker_moe.py``: windowed and global layers in one
stack, 7 query heads a key-value head).  The tile edges are a function of the
mask (:func:`splash_blocks`): under a window narrower than those tiles
(``models/laguna.py``: 512 keys, 8 query heads a key-value head) the edges are
512 and the backward pass is two kernels, ``dK`` and ``dV`` in one and ``dQ``
in the other, so that no ``dQ`` partial is written for the key blocks a query
block never sees.  A window that holds the whole
sequence *is* the causal mask and runs as ``window=None`` does; and
``window=None`` builds and runs what it did before there was a window.
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
#: the edges under a window narrower than a tile above, where those tiles are
#: mostly masked scores: 512 keys at 8,192 positions leave 4.06 M pairs open, and
#: tiles of 1,024 (8 on the diagonal, 7 beside it) run 15.7 M.  Decided on the
#: v5e at 64 query heads on 8 key-value heads x 8,192 x 128 under a window of 512
#: by a plain SGD step's time of one such layer (PERF.md section 6, PR 49,
#: twenty-seven variants): every edge 512 and the backward pass as *two* kernels
#: (``splash_mqa_dkv`` and ``splash_mqa_dq``) 56.36 ms, the tiles above 59.29;
#: the fused backward writes one ``dQ`` partial per ``block_kv_dkv`` keys, sixteen
#: at 512 of which two a query block hold anything, and read 60.18 at these edges
#: and 80.54 at 256; 256 or 1,024 on any one side of any of the three kernels
#: 56.96 to 58.12
SPLASH_NARROW_BLOCKS = dict(
    block_q=512, block_kv=512, block_kv_compute=512,
    block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512,
    block_q_dq=512, block_kv_dq=512, use_fused_bwd_kernel=False)


def splash_blocks(t: int, window=None) -> dict:
    """The tile edges of the splash kernels for ``t`` positions under the
    causal mask (``window=None``) or under a window of so many keys: the mask
    decides how much of a tile is work.  No window, and a window of at least a
    tile's keys (``models/smallthinker_moe.py``: 4,096 at 8,192), take
    :data:`SPLASH_BLOCKS`, whose backward pass is the one fused kernel; a
    narrower one (``models/laguna.py``: 512 at 8,192, the one measured) takes
    :data:`SPLASH_NARROW_BLOCKS`, which says so where its backward is two,
    wherever its edges divide the positions."""
    narrow = SPLASH_NARROW_BLOCKS
    if window is None or window >= SPLASH_BLOCKS["block_kv"] or t % narrow["block_q"]:
        return SPLASH_BLOCKS
    return narrow


_NEG = -1e30  # finite: a masked score must not make ``exp(s - m)`` a NaN


def causal_attention(q, k, v, scale: float, window=None):
    """``softmax(q k^T * scale + causal mask) v``; with ``window``, the mask
    also hides key ``j`` from position ``i`` where ``i - j >= window``.
    ``q``, ``k``, ``v`` are
    ``(batch, heads, positions, head size)`` with one head size, ``k`` and
    ``v`` with ``q``'s heads or a divisor of them (grouped queries: the
    module's text); the result has ``q``'s shape and type.  On a TPU the
    kernels take no scale, so what is computed there is ``softmax((q * scale) k^T + causal mask) v`` with ``q * scale``
    rounded to ``q``'s type: the same numbers where ``scale`` is a power of
    two, one more rounding of ``q`` where it is not.  A ``scale`` of 1 says
    that the caller's ``q`` carries the scale already (``models/glm_moe.py``
    multiplies in float32 in the pass that rounds ``q``): ``q`` then goes to
    the kernels as it came, with no pass and no rounding of its own."""
    t = q.shape[2]
    if k.shape != v.shape or q.shape[1] % k.shape[1]:
        raise ValueError(f"k {k.shape} and v {v.shape} must be one shape whose heads divide "
                         f"q's {q.shape[1]}")
    if window is not None:
        if window < 1:
            raise ValueError(f"a window of {window} keys holds not even the current position")
        if window >= t:  # every earlier key is inside it: the causal mask
            window = None
    if jax.default_backend() == "tpu" and t % SPLASH_BLOCK_MAJOR == 0:
        return _splash_causal_attention(q, k, v, scale, window=window)
    return blocked_causal_attention(q, k, v, scale, min(BLOCK_Q, t), window)


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, t: int, interpret: bool = False, multi_query: bool = False,
                   window=None):
    """The kernels of one ``(heads, t, window)``, built once: the mask's block
    tables are made on the host in numpy, and every layer of a model with
    that mask shares them.  ``multi_query``: ``heads`` query heads on one
    key-value head.  ``window``: ``window - 1`` keys to the left of the
    diagonal and none to its right."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    one = (masks.CausalMask((t, t)) if window is None
           else masks.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    mask = masks.MultiHeadMask([one] * heads)
    make = splash.make_splash_mqa if multi_query else splash.make_splash_mha
    blocks = splash.BlockSizes(**{"use_fused_bwd_kernel": True, **splash_blocks(t, window)})
    with jax.ensure_compile_time_eval():  # the tables are constants of whatever trace asks first
        return make(mask, block_sizes=blocks, head_shards=1, q_seq_shards=1, interpret=interpret)


def _splash_causal_attention(q, k, v, scale: float, interpret: bool = False, window=None):
    b, heads, t, size = q.shape
    kv_heads = k.shape[1]
    if scale != 1.0:
        q = (q * scale).astype(q.dtype)
    if kv_heads == heads:
        return jax.vmap(_splash_kernel(heads, t, interpret, window=window))(q, k, v)
    # each key-value head with its group of query heads: one k, one v a group
    group = heads // kv_heads
    kernel = _splash_kernel(group, t, interpret, multi_query=True, window=window)
    out = jax.vmap(jax.vmap(kernel))(q.reshape(b, kv_heads, group, t, size), k, v)
    return out.reshape(b, heads, t, size)


def _blocks(t: int, block_q: int):
    if t % block_q:
        raise ValueError(f"{t} positions do not divide into query blocks of {block_q}")
    return [(i * block_q, (i + 1) * block_q) for i in range(t // block_q)]


def _rows_by_group(x, kv_heads: int, start: int, end: int):
    """Positions ``start .. end`` of ``x (batch, heads, positions, size)``
    with each key-value head's group of query heads laid along the rows:
    ``(batch, key-value heads, group x (end - start), size)``."""
    b, heads, _, d = x.shape
    return x[:, :, start:end].reshape(b, kv_heads, heads // kv_heads * (end - start), d)


def _first_key(start: int, window) -> int:
    """The first key any query of the block that begins at ``start`` sees."""
    return 0 if window is None else max(0, start - window + 1)


def _block_scores(q_blk, k_seen, start: int, block_q: int, scale: float, first: int = 0,
                  window=None):
    """Float32 scores of a group's query rows at positions ``start .. start +
    block_q`` against keys ``first .. end``, masked above the diagonal and,
    with ``window``, behind the window."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_seen,
                   preferred_element_type=jnp.float32) * scale
    rows = start + jnp.tile(jnp.arange(block_q), q_blk.shape[2] // block_q)[:, None]
    keys = jnp.arange(k_seen.shape[2])[None, :]
    if window is None:
        return jnp.where(keys <= rows, s, _NEG)
    keys = keys + first
    return jnp.where((keys <= rows) & (rows - keys < window), s, _NEG)


def _forward(q, k, v, scale, block_q, window=None):
    b, heads, t, d = q.shape
    kv_heads = k.shape[1]
    outs, lses = [], []
    for start, end in _blocks(t, block_q):
        first = _first_key(start, window)
        s = _block_scores(_rows_by_group(q, kv_heads, start, end), k[:, :, first:end],
                          start, block_q, scale, first, window)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, first:end],
                       preferred_element_type=jnp.float32) / l
        outs.append(o.astype(q.dtype).reshape(b, heads, block_q, d))
        lses.append((m + jnp.log(l))[..., 0].reshape(b, heads, block_q))
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blocked_causal_attention(q, k, v, scale: float, block_q: int, window=None):
    return _forward(q, k, v, scale, block_q, window)[0]


def _fwd(q, k, v, scale, block_q, window):
    out, lse = _forward(q, k, v, scale, block_q, window)
    return out, (q, k, v, out, lse)


def _bwd(scale, block_q, window, res, d_out):
    q, k, v, out, lse = res
    f32 = jnp.float32
    b, heads, t, d = q.shape
    kv_heads = k.shape[1]
    # softmax's backward needs each row's sum of p * dp, which is do . o
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1, keepdims=True)
    dq = []
    dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    for start, end in _blocks(t, block_q):
        q_blk, do_blk = (_rows_by_group(x, kv_heads, start, end) for x in (q, d_out))
        first = _first_key(start, window)
        s = _block_scores(q_blk, k[:, :, first:end], start, block_q, scale, first, window)
        p = jnp.exp(s - _rows_by_group(lse[..., None], kv_heads, start, end))
        # the sums over the rows are sums over a group's query heads too
        dv = dv.at[:, :, first:end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(v.dtype), do_blk, preferred_element_type=f32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, v[:, :, first:end], preferred_element_type=f32)
        ds = (p * (dp - _rows_by_group(delta, kv_heads, start, end)) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhqk,bhkd->bhqd", ds, k[:, :, first:end],
                             preferred_element_type=f32).astype(q.dtype).reshape(
                                 b, heads, block_q, d))
        dk = dk.at[:, :, first:end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_blk, preferred_element_type=f32))
    return jnp.concatenate(dq, axis=2), dk.astype(k.dtype), dv.astype(v.dtype)


blocked_causal_attention.defvjp(_fwd, _bwd)
