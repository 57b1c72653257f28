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

import dataclasses
import math
from typing import Any, Optional, Tuple

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


def rotary(x, inv_freq, scale: float = 1.0, factor: float = 1.0):
    """The rotary embedding from its tables, in float32: the first ``2 *
    len(inv_freq)`` columns of ``x (..., positions, size)`` are turned in the
    rotate-half pairing (column ``i`` with ``i + len(inv_freq)``, by ``position
    * inv_freq[i]``) with ``cos`` and ``sin`` times ``factor``; the columns
    after them pass through unrotated and without the factor; all times
    ``scale``."""
    t, size = x.shape[-2:]
    half = inv_freq.shape[0]
    ang = jnp.arange(t).astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * (scale * factor), jnp.sin(ang) * (scale * factor)
    first, second = x[..., :half].astype(jnp.float32), x[..., half:2 * half].astype(jnp.float32)
    turned = [first * cos - second * sin, second * cos + first * sin]
    if 2 * half < size:
        turned.append(x[..., 2 * half:].astype(jnp.float32) * scale)
    return jnp.concatenate(turned, axis=-1)


def rotate_half(x, theta: float, scale: float = 1.0):
    """The rotary embedding on all columns of ``x (..., positions, size)`` in
    the rotate-half pairing (column ``i`` with ``i + size / 2``), in float32,
    times ``scale``: :func:`rotary` with the tables ``theta ** (-2i / size)``."""
    size = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, size, 2, dtype=jnp.float32) / size))
    return rotary(x, inv_freq, scale)


@dataclasses.dataclass(frozen=True)
class RotaryTables:
    """A rotary embedding as its tables: ``inv_freq`` a pair (twice as many
    columns are rotated, the first of a head's; the others pass through) and
    the ``factor`` on ``cos`` and ``sin``.  ``rope_theta`` of
    :class:`GroupedQueryAttention` is the case ``theta ** (-2i / head size)``
    over all columns with factor 1."""

    inv_freq: Tuple[float, ...]
    factor: float = 1.0

    @property
    def columns(self) -> int:
        return 2 * len(self.inv_freq)


class GroupedQueryAttention(Kernels):
    """``softmax(q k^T / sqrt(head_size) + causal) v W_o``: ``q`` onto ``heads``
    heads, ``k`` and ``v`` onto ``kv_heads``, each serving ``heads / kv_heads``
    query heads.  The rest a model's configuration states: ``norm_eps``, an
    :class:`RMSNorm` over every head of ``q`` and of ``k`` (``q_norm``,
    ``k_norm``) or none; ``rope_theta``, :func:`rotate_half` on ``q`` and ``k``
    with the score's scale in ``q``'s tables, or ``rope``, the tables
    themselves (:class:`RotaryTables`: fewer columns than a head has, blended
    frequencies, a factor), or no position and ``q`` times the scale;
    ``window``, so many keys counting the current one (under
    ``attn_window_core``, so that a capture reads the two masks apart) or all
    earlier keys (``attn_core``); ``gate``, a scalar a head and position,
    ``sigmoid(x W_g)`` from the layer's own input, on the core's result before
    ``W_o`` (under ``attn_gate``)."""

    heads: int
    kv_heads: int
    head_size: int
    compute_dtype: Any
    norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None
    window: Optional[int] = None
    rope: Optional[RotaryTables] = None
    gate: bool = False

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
        if self.rope is not None and (self.rope_theta is not None or self.rope.columns > size):
            raise ValueError(f"rope_theta {self.rope_theta} beside tables, or tables of "
                             f"{self.rope.columns} columns for heads of {size}")

        def heads_of(name, count, scale=None):
            """One projection as the kernels read it: the product alone where nothing
            follows it, else normed, rotated and scaled in float32 and rounded once."""
            kernel = self.kernel(name + "_proj", hidden, count * size).reshape(hidden, count, size)
            plain = self.norm_eps is None and self.rope_theta is None and self.rope is None
            if scale is None or (plain and scale == 1.0):
                return product(HEADS_MAJOR, x, kernel, dt)
            y = jnp.einsum(HEADS_MAJOR, x.astype(dt), kernel.astype(dt),
                           preferred_element_type=jnp.float32)
            if self.norm_eps is not None:
                y = RMSNorm(self.norm_eps, name=name + "_norm")(y)
            if self.rope is not None:
                return rotary(y, jnp.asarray(self.rope.inv_freq, jnp.float32), scale,
                              self.rope.factor).astype(dt)
            if self.rope_theta is None:
                return (y * scale).astype(dt)
            return rotate_half(y, self.rope_theta, scale).astype(dt)

        with model_scope("attn_proj"):
            q = heads_of("q", self.heads, 1.0 / math.sqrt(size))
            k = heads_of("k", self.kv_heads, 1.0)
            v = heads_of("v", self.kv_heads)
            out = self.kernel("out_proj", self.heads * size, hidden).reshape(-1, size, hidden)
        ctx = self.core(q, k, v)
        if self.gate:
            with model_scope("attn_gate"):
                opened = jax.nn.sigmoid(jnp.einsum(
                    "btm,mh->bth", x.astype(dt), self.kernel("gate_proj", hidden, self.heads).astype(dt),
                    preferred_element_type=jnp.float32))
                ctx = (ctx * opened.swapaxes(1, 2)[..., None]).astype(dt)
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
