"""The parts the causal decoder models share, defined once: ``glm_moe.py``,
``lfm2_moe.py``, ``smallthinker_moe.py``, ``ouro.py``, ``nemotron_h.py``,
``laguna.py`` and ``solar_open2.py`` build on this module and on no other model file (``llama.py`` takes ``RMSNorm``
from here).  Tested in ``tests/test_decoder.py`` and, the kernel under the
attention, ``tests/test_causal_attention.py``, and not again in a model's file.

How the attention's operands are written (``PERF.md`` section 6, PR 32, 33 and
50).  The attention kernels read ``(batch, heads, positions, head size)``, so
the three products contract onto that layout, one float32 pass norms, rotates,
scales (``q`` carries ``1 / sqrt(head size)``) and rounds each of ``q`` and
``k``, and ``W_o`` contracts the kernels' result over ``(heads, head size)``.
That no array with the positions in it is transposed on the way was read off
the compiled steps of ``glm-4.7-flash`` (20 heads of 256, latent attention of
its own) and ``lfm2-8b-a1b`` (32 heads of 64 on 8) when PR 32 and 33 wrote the
layer, and holds for ``ouro-2.6b`` (16 heads of 128, a key-value head a query
head: not one ``copy`` of a ``(16, 8192, 128)`` array; the census of ISSUE 50).
It did not hold under *grouped queries at heads of 128*: at 28 heads on 4
(``smallthinker-21ba3b``) and at 48 and 64 on 8 (``laguna-xs.2``) the TPU
compiler wrote the ``q`` product's float32 result with the positions minor, so
that the rotation's slices at column 64 would not cut a 128-lane tile, turned
it back for the kernel, and did the same to the kernel's result around the
gate and to both cotangents: 4.6 GB a windowed layer of Laguna's where 1.4
would do.  So where ``head_size % 128 == 0 and heads > kv_heads`` (all of it
the layer's own fields) the rotation and the gate are one pass each with a
backward rule of its own, in the kernels' layout
(``kernels/head_passes.py``: Pallas calls on a TPU, whose operands and
results have the row-major layout; the same formulas in ``jax.numpy``
elsewhere), and what no pass touches (``q`` without positions, ``ctx`` without
a gate) is held to that layout by a constraint, which costs no pass.  Any other
layer is written as it was, and its step's text is what it was.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.head_passes import gate_heads, rotary_tables, row_major, turn_heads
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


def _taps_and_bias(xbc, taps, bias):
    """``bias + sum_i taps[i] * xbc_{t - (L - 1) + i}`` in float32: the last
    tap meets the current position."""
    last = taps.shape[0] - 1
    x = xbc.astype(jnp.float32)
    def summed():
        return sum(taps[i].astype(jnp.float32) * shift(x, last - i) for i in range(last + 1))

    return summed() if bias is None else bias.astype(jnp.float32) + summed()


@jax.custom_vjp
def causal_conv_silu(xbc, taps, bias=None):
    """``silu(conv(xbc) + bias)``, ``(batch, positions, channels)`` in
    ``xbc``'s type: depthwise, causal (zeros before the start), ``taps (L,
    channels)`` with the last tap on the current position, ``bias (channels,)``
    or none; in float32 and rounded once.  The backward pass keeps ``xbc`` and
    builds the sum again.  The short convolution of ``nemotron_h.py``'s Mamba-2
    mixer (with its bias) and of ``solar_open2.py``'s KDA mixer (without);
    ``lfm2_moe.gated_short_conv`` is another arithmetic, gates between the
    taps."""
    return jax.nn.silu(_taps_and_bias(xbc, taps, bias)).astype(xbc.dtype)


def _causal_conv_silu_fwd(xbc, taps, bias):
    return causal_conv_silu(xbc, taps, bias), (xbc, taps, bias)


def _causal_conv_silu_bwd(res, dy):
    xbc, taps, bias = res
    last = taps.shape[0] - 1
    pre = _taps_and_bias(xbc, taps, bias)
    gate = jax.nn.sigmoid(pre)
    d_pre = dy.astype(jnp.float32) * gate * (1.0 + pre * (1.0 - gate))
    x = xbc.astype(jnp.float32)
    # x_t feeds position t + (L - 1) - i through tap i: the taps run against time
    d_x = sum(taps[i].astype(jnp.float32) * shift(d_pre, i - last) for i in range(last + 1))
    d_taps = jnp.stack([jnp.sum(d_pre * shift(x, last - i), axis=(0, 1)) for i in range(last + 1)])
    return (d_x.astype(xbc.dtype), d_taps.astype(taps.dtype),
            None if bias is None else jnp.sum(d_pre, axis=(0, 1)).astype(bias.dtype))


causal_conv_silu.defvjp(_causal_conv_silu_fwd, _causal_conv_silu_bwd)


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


def _inv_freq(theta: float, size: int):
    return 1.0 / (theta ** (jnp.arange(0, size, 2, dtype=jnp.float32) / size))


def rotate_half(x, theta: float, scale: float = 1.0):
    """The rotary embedding on all columns of ``x (..., positions, size)`` in
    the rotate-half pairing (column ``i`` with ``i + size / 2``), in float32,
    times ``scale``: :func:`rotary` with the tables ``theta ** (-2i / size)``."""
    return rotary(x, _inv_freq(theta, x.shape[-1]), scale)


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
    earlier keys (``attn_core``); ``gate``, ``sigmoid(x W_g)`` from the layer's
    own input on the core's result before ``W_o`` (under ``attn_gate``), at one
    of two widths: ``True``, a scalar a head and position (``W_g`` of ``hidden x
    heads``), or ``"column"``, one value a head *column* and position (``hidden
    x heads x head size``).  Grouped queries at heads of whole lane tiles
    take the rotation and the gate as ``kernels/head_passes.py``'s passes, the
    same arithmetic (the module's text)."""

    heads: int
    kv_heads: int
    head_size: int
    compute_dtype: Any
    norm_eps: Optional[float] = None
    rope_theta: Optional[float] = None
    window: Optional[int] = None
    rope: Optional[RotaryTables] = None
    gate: Any = False

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
        # grouped queries at whole lane tiles: the entry and the exit are pinned (the module's text)
        pinned = size % 128 == 0 and self.heads > self.kv_heads
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
            if self.rope is None and self.rope_theta is None:
                y = (y * scale).astype(dt)
                return row_major(y) if pinned else y
            if self.rope is not None:
                inv_freq, factor = jnp.asarray(self.rope.inv_freq, jnp.float32), self.rope.factor
            else:
                inv_freq, factor = _inv_freq(self.rope_theta, size), 1.0
            if pinned:
                return turn_heads(y, *rotary_tables(inv_freq, y.shape[2], size, scale, factor), dt)
            return rotary(y, inv_freq, scale, factor).astype(dt)

        with model_scope("attn_proj"):
            q = heads_of("q", self.heads, 1.0 / math.sqrt(size))
            k = heads_of("k", self.kv_heads, 1.0)
            v = heads_of("v", self.kv_heads)
            out = self.kernel("out_proj", self.heads * size, hidden).reshape(-1, size, hidden)
        ctx = self.core(q, k, v)
        if self.gate == "column":
            with model_scope("attn_gate"):
                by_column = self.kernel("gate_proj", hidden, self.heads * size)
                opened = jax.nn.sigmoid(jnp.einsum(
                    HEADS_MAJOR, x.astype(dt), by_column.reshape(hidden, -1, size).astype(dt),
                    preferred_element_type=jnp.float32))
                ctx = (ctx * opened).astype(dt)
                ctx = row_major(ctx) if pinned else ctx
        elif self.gate:
            with model_scope("attn_gate"):
                opened = jax.nn.sigmoid(jnp.einsum(
                    "btm,mh->bth", x.astype(dt), self.kernel("gate_proj", hidden, self.heads).astype(dt),
                    preferred_element_type=jnp.float32))
                if pinned:
                    ctx = gate_heads(ctx, opened)
                else:
                    ctx = (ctx * opened.swapaxes(1, 2)[..., None]).astype(dt)
        elif pinned:
            ctx = row_major(ctx)
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
