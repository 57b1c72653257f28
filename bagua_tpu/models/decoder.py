"""The parts the causal decoder models share, defined once: ``glm_moe.py``,
``lfm2_moe.py``, ``smallthinker_moe.py``, ``ouro.py`` and ``nemotron_h.py``
build on this module and on no other model file (``llama.py`` takes ``RMSNorm``
from here).  Tested in ``tests/test_decoder.py`` and, the kernel under the
attention, ``tests/test_causal_attention.py``, and not again in a model's file.

How the attention's operands are written (``PERF.md`` section 6, PR 32 and
33).  The attention kernels read ``(batch, heads, positions, head size)``, so
the three products contract onto that layout, one float32 pass norms, rotates,
scales (``q`` carries ``1 / sqrt(head size)``) and rounds each of ``q`` and
``k``, and ``W_o`` contracts the kernels' result over ``(heads, head size)``:
no array with the positions in it is transposed.
"""

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.observability.annotations import model_scope


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(dtype)


def matmul(x, kernel, dtype):
    """``x @ kernel`` with ``dtype`` operands, float32 accumulation, ``dtype``
    result."""
    return jnp.dot(x.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


class Kernels(nn.Module):
    """Float32 kernels, normal(0, 0.02), declared by shape."""

    def kernel(self, name: str, *shape: int):
        return self.param(name, nn.initializers.normal(0.02), shape, jnp.float32)


def product(pattern: str, x, kernel, dtype):
    """``einsum(pattern, x, kernel)`` with ``dtype`` operands, float32
    accumulation, ``dtype`` result: the contraction writes the layout its
    reader takes."""
    return jnp.einsum(pattern, x.astype(dtype), kernel.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


#: ``(batch, positions, rank)`` times ``(rank, heads, size)`` as the attention
#: kernels read it, ``(batch, heads, positions, size)``
HEADS_MAJOR = "btr,rhd->bhtd"


class SwiGLU(Kernels):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        h = jax.nn.silu(matmul(x, self.kernel("gate", hidden, self.width), self.dtype))
        h = h * matmul(x, self.kernel("up", hidden, self.width), self.dtype)
        return matmul(h, self.kernel("down", self.width, hidden), self.dtype)


def shift(x, by: int, axis: int = 1):
    """``x`` moved ``by`` positions later along ``axis`` (earlier if
    negative), zeros moving in."""
    if by == 0:
        return x
    t = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (max(by, 0), max(-by, 0))
    return jax.lax.slice_in_dim(jnp.pad(x, pad), max(-by, 0), max(-by, 0) + t, axis=axis)


def rotate_half(x, theta: float, scale: float = 1.0):
    """The rotary embedding on all columns of ``x (..., positions, size)`` in
    the rotate-half pairing (column ``i`` with ``i + size / 2``), in float32,
    times ``scale``."""
    t, size = x.shape[-2:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, size, 2, dtype=jnp.float32) / size))
    ang = jnp.arange(t).astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    first, second = x[..., :size // 2].astype(jnp.float32), x[..., size // 2:].astype(jnp.float32)
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


class GroupedQueryAttention(Kernels):
    """``softmax(q k^T / sqrt(head_size) + causal) v W_o``: ``q`` onto ``heads``
    heads, ``k`` and ``v`` onto ``kv_heads``, each serving ``heads / kv_heads``
    query heads.  The rest a model's configuration states: ``norm_eps``, an
    :class:`RMSNorm` over every head of ``q`` and of ``k`` (``q_norm``,
    ``k_norm``) or none; ``rope_theta``, :func:`rotate_half` on ``q`` and ``k``
    with the score's scale in ``q``'s tables, or no position and ``q`` times
    the scale; ``window``, so many keys counting the current one (under
    ``attn_window_core``, so that a capture reads the two masks apart) or all
    earlier keys (``attn_core``)."""

    heads: int
    kv_heads: int
    head_size: int
    compute_dtype: Any
    norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None
    window: Optional[int] = None

    @nn.nowrap
    def core(self, q, k, v):
        """The kernel under its scope: a method, so that a model can have the
        kernel looked up in its own module (``models/smallthinker_moe.py``)."""
        with model_scope("attn_core" if self.window is None else "attn_window_core"):
            return causal_attention(q, k, v, 1.0, window=self.window)

    @nn.compact
    def __call__(self, x):
        dt, size = self.compute_dtype, self.head_size
        hidden = x.shape[-1]

        def heads_of(name, count, scale=None):
            """One projection as the kernels read it: the product alone where nothing
            follows it, else normed, rotated and scaled in float32 and rounded once."""
            kernel = self.kernel(name + "_proj", hidden, count * size).reshape(hidden, count, size)
            plain = self.norm_eps is None and self.rope_theta is None
            if scale is None or (plain and scale == 1.0):
                return product(HEADS_MAJOR, x, kernel, dt)
            y = jnp.einsum(HEADS_MAJOR, x.astype(dt), kernel.astype(dt),
                           preferred_element_type=jnp.float32)
            if self.norm_eps is not None:
                y = RMSNorm(self.norm_eps, name=name + "_norm")(y)
            if self.rope_theta is None:
                return (y * scale).astype(dt)
            return rotate_half(y, self.rope_theta, scale).astype(dt)

        with model_scope("attn_proj"):
            q = heads_of("q", self.heads, 1.0 / math.sqrt(size))
            k = heads_of("k", self.kv_heads, 1.0)
            v = heads_of("v", self.kv_heads)
            out = self.kernel("out_proj", self.heads * size, hidden).reshape(-1, size, hidden)
        ctx = self.core(q, k, v)
        with model_scope("attn_proj"):
            return product("bhtd,hdm->btm", ctx, out, dt)


def next_token_loss_fn(model):
    """Next-token cross entropy, mean over each sequence's ``positions - 1``
    targets: the loss of any model of ids to logits.  ``batch`` is the ids
    alone."""

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch)
        # every row against the token that follows it, the rows without one
        # left out of the mean: a slice of the logits would be a copy of them
        return jnp.mean(softmax_cross_entropy(logits, jnp.roll(batch, -1, axis=1))[:, :-1])

    return loss_fn
