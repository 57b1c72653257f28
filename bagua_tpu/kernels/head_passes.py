"""The two element-wise passes at the ends of an attention kernel, each one
read and one write of a ``(batch, heads, positions, head size)`` array in the
layout the splash kernels read (head size on the lanes), each with its own
backward rule.

* :func:`turn_heads`, the entry: a projection's float32 result in, the rotary
  embedding times the score's scale, rounded once, out.  The rotate-half
  pairing is a *rotation of the lanes* against full-width tables
  (:func:`rotary_tables`), never two slices inside a 128-lane tile: where every
  column turns, by half the head against ``[cos, cos]`` and ``[-sin, sin]``;
  where the tables cover fewer columns than the head has (YaRN on 64 of 128,
  pairs 32 apart) by ``-half`` and by ``+half``, each against a sine table that
  is zero outside the columns it serves, the cosine table the scale over the
  columns that pass through.  Backward: the transposed rotation of the
  cotangent, one pass, written in the type the forward pass rounds to where
  the products that read it would round it so themselves.
* :func:`gate_heads`, the exit: the kernel's ``ctx`` and a float32 ``a (batch,
  positions, heads)`` in, ``ctx * a`` in ``ctx``'s type out, the
  multiplication in float32.  Backward: ``d ctx = d * a`` in ``ctx``'s type
  and ``d a = sum over a head's columns of d * ctx`` in float32, one pass.

Why they are kernels (``PERF.md`` section 6, PR 50).  Under grouped queries
at heads of 128 the TPU compiler laid the ``q`` product's result out with the
*positions* minor, so that the rotation's slices at column 64 would not cut a
lane tile, and turned the array back for the attention kernel; it did the same
to the kernel's result around the gate and to both cotangents: in
``laguna-xs.2.dp1-s8192`` 4.6 GB a windowed layer of float32 copies and
transposes where 1.4 would do.  A Pallas call's operands and results have the
row-major tiled layout, which takes that choice away.  The arithmetic is
``models/decoder.py``'s ``rotary`` and gate to the bit (a float32 rotation and
multiplication, one rounding), up to the sign of a zero and the order of ``d
a``'s sum.

Two implementations, one per backend, chosen by ``jax.default_backend()`` and
the shapes and by no option: the Pallas kernels on a TPU, the same formulas in
``jax.numpy`` under autodiff elsewhere and for shapes the kernels do not take
(a head size that is no multiple of 128 lanes, positions that do not divide
into blocks of 16 rows).  The CPU reaches the kernels' bodies through Pallas'
interpreter (``interpret=True``; ``tests/test_decoder.py``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: the bytes of the largest block a grid step moves: with two buffers each of
#: three such blocks (the gate's backward pass) 12 MB of a kernel's 16
_BLOCK_BYTES = 2 << 20


def _parallel(grid):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * len(grid))


def rotary_tables(inv_freq, positions: int, size: int, scale: float = 1.0, factor: float = 1.0):
    """``(tables, shifts)`` of the rotary embedding ``models/decoder.py``'s
    ``rotary`` applies from ``inv_freq``, ``scale`` and ``factor``, as
    :func:`turn_heads` takes them: ``tables (1 + len(shifts), positions, size)``
    float32 with ``turn_heads(y) = y * tables[0] + sum_k roll(y, shifts[k]) *
    tables[1 + k]`` along the columns."""
    f32 = jnp.float32
    half = inv_freq.shape[0]
    ang = jnp.arange(positions).astype(f32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * (scale * factor), jnp.sin(ang) * (scale * factor)
    if 2 * half == size:  # column j reads its partner half a head away, either way round
        return jnp.stack([jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1)]), (half,)
    rest = jnp.zeros((positions, size - 2 * half), f32)
    none = jnp.zeros_like(sin)
    return jnp.stack([
        jnp.concatenate([cos, cos, jnp.full_like(rest, scale)], -1),
        jnp.concatenate([-sin, none, rest], -1),   # on the columns moved down from half above
        jnp.concatenate([none, sin, rest], -1),    # on the columns moved up from half below
    ]), (size - half, half)


def _rows(x, heads: int, itemsize: int):
    """The positions a block of ``x (batch, heads, positions, size)`` that
    holds ``heads`` heads of ``itemsize`` bytes an element: the largest power
    of two whose rows fit :data:`_BLOCK_BYTES` and divide the positions.  None:
    not the kernels' shape (a head size that is no whole number of lane tiles,
    or under 16 such rows, a ``bfloat16`` tile's)."""
    if x.ndim != 4 or x.shape[3] % _LANES:
        return None
    t = x.shape[2]
    rows = 1 << max(_BLOCK_BYTES // (heads * x.shape[3] * itemsize), 1).bit_length() - 1
    while rows > 16 and t % rows:
        rows //= 2
    return rows if rows >= 16 and t % rows == 0 else None


def _heads_a_block(heads: int) -> int:
    """Of the entry pass: the most that divide the heads, up to 8."""
    return max(n for n in range(1, 9) if heads % n == 0)


def _turn_rows(x):
    """Of the entry pass, float32 on its wider side."""
    return _rows(x, _heads_a_block(x.shape[1]), 4)


def _gate_rows(ctx):
    """Of the exit pass, all heads a block."""
    return _rows(ctx, ctx.shape[1], ctx.dtype.itemsize)


def held_to(x, major_to_minor):
    """``x`` held to a layout on a TPU (its dimensions from the outermost to
    the one along the lanes), and its cotangent with it: no pass, a constraint
    on the compiler's choice for a product's result that no kernel of this
    module reads or writes."""
    if jax.default_backend() != "tpu":
        return x
    return with_layout_constraint(x, Layout(major_to_minor=tuple(major_to_minor)))


def row_major(x):
    """:func:`held_to` the row-major layout."""
    return held_to(x, range(x.ndim))


# ---- the entry ----------------------------------------------------------------------------


def _turned(y, tables, shifts, dtype):
    y = y.astype(jnp.float32)
    out = y * tables[0]
    for k, shift in enumerate(shifts, 1):
        out = out + jnp.roll(y, shift, axis=-1) * tables[k]
    return out.astype(dtype)


def _turn_kernel(x_ref, tables_ref, out_ref, *, shifts, back: bool):
    """``back``: the transposed rotation, ``x * tables[0] + sum_k roll(x *
    tables[k], -shifts[k])``: what column ``j`` gave column ``j + shift``
    comes back from there."""
    size = x_ref.shape[-1]
    for h in range(x_ref.shape[0]):
        x = x_ref[h].astype(jnp.float32)
        out = x * tables_ref[0]
        for k, shift in enumerate(shifts, 1):
            if back:
                out = out + pltpu.roll(x * tables_ref[k], size - shift, 1)
            else:
                out = out + pltpu.roll(x, shift, 1) * tables_ref[k]
        out_ref[h] = out.astype(out_ref.dtype)


# The calls are jitted on the kernels' own operands, so that a model's layers of one shape
# share one trace and one lowering of each kernel (``kernels/ssd_scan.py`` does the same).


@functools.partial(jax.jit, static_argnames=("shifts", "back", "dtype", "interpret"))
def _turn_call(x, tables, *, shifts, back, dtype, interpret):
    batch, heads, t, size = x.shape
    per, rows = _heads_a_block(heads), _turn_rows(x)
    block = pl.BlockSpec((None, per, rows, size), lambda b, i, h: (b, h, i, 0))
    grid = (batch, t // rows, heads // per)  # the heads innermost: a block of the tables is read once for all
    return pl.pallas_call(
        functools.partial(_turn_kernel, shifts=shifts, back=back), grid=grid,
        in_specs=[block, pl.BlockSpec((tables.shape[0], rows, size), lambda b, i, h: (0, i, 0))],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(x.shape, dtype),
        compiler_params=_parallel(grid), interpret=interpret,
        name="head_turn_backward" if back else "head_turn",
    )(x, tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _turn_kernels(y, tables, shifts, dtype, interpret=False):
    return _turn_call(y, tables, shifts=shifts, back=False, dtype=dtype, interpret=interpret)


def _turn_kernels_fwd(y, tables, shifts, dtype, interpret):
    return _turn_call(y, tables, shifts=shifts, back=False, dtype=dtype, interpret=interpret), tables


def _turn_kernels_bwd(shifts, dtype, interpret, tables, d):
    # The cotangent's two readers are products (the input's and the kernel's gradients), and at
    # the TPU's default precision a product rounds a float32 operand to bfloat16 itself: written
    # in the type the forward pass rounds to, it is half the bytes and the same gradients to the
    # bit (a one-layer step on the chip, both kinds of layer; PERF.md section 6, PR 50).  Under a
    # precision that would keep the float32 operand it stays float32.
    one_pass = jax.config.jax_default_matmul_precision in (None, "default", "bfloat16")
    dy = _turn_call(d, tables, shifts=shifts, back=True, interpret=interpret,
                    dtype=dtype if one_pass else jnp.dtype(jnp.float32))
    return dy.astype(jnp.float32), jnp.zeros_like(tables)  # the tables are constants of the step


_turn_kernels.defvjp(_turn_kernels_fwd, _turn_kernels_bwd)


def turn_heads(y, tables, shifts, dtype):
    """``y (batch, heads, positions, size)`` float32, rotated against
    ``tables`` along its columns (:func:`rotary_tables`) and rounded to
    ``dtype``."""
    if y.dtype != jnp.float32:
        raise ValueError(f"the entry pass takes a product's float32 result, not {y.dtype}")
    if jax.default_backend() == "tpu" and _turn_rows(y):
        return _turn_kernels(y, tables, tuple(shifts), jnp.dtype(dtype))
    return _turned(y, tables, shifts, dtype)


# ---- the exit -----------------------------------------------------------------------------


def _gated(ctx, a):
    return (ctx * a.swapaxes(1, 2)[..., None]).astype(ctx.dtype)


def _gate_kernel(ctx_ref, a_ref, out_ref):
    a = a_ref[...]
    for h in range(ctx_ref.shape[0]):
        out_ref[h] = (ctx_ref[h].astype(jnp.float32) * a[:, h:h + 1]).astype(out_ref.dtype)


def _gate_back_kernel(d_ref, ctx_ref, a_ref, dctx_ref, da_ref):
    a = a_ref[...]
    for h in range(ctx_ref.shape[0]):
        d = d_ref[h].astype(jnp.float32)
        dctx_ref[h] = (d * a[:, h:h + 1]).astype(dctx_ref.dtype)
        da_ref[:, h:h + 1] = jnp.sum(d * ctx_ref[h].astype(jnp.float32), axis=1, keepdims=True)


def _gate_specs(ctx):
    """A grid step is a block of positions, all heads: ``a``'s block has the
    heads along its lanes, whole."""
    batch, heads, t, size = ctx.shape
    rows = _gate_rows(ctx)
    wide = pl.BlockSpec((None, heads, rows, size), lambda b, i: (b, 0, i, 0))
    return (batch, t // rows), wide, pl.BlockSpec((None, rows, heads), lambda b, i: (b, i, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gate_call(ctx, a, *, interpret):
    grid, wide, narrow = _gate_specs(ctx)
    return pl.pallas_call(
        _gate_kernel, grid=grid, in_specs=[wide, narrow], out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(ctx.shape, ctx.dtype),
        compiler_params=_parallel(grid), interpret=interpret, name="head_gate",
    )(ctx, a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gate_back_call(d, ctx, a, *, interpret):
    grid, wide, narrow = _gate_specs(ctx)
    return pl.pallas_call(
        _gate_back_kernel, grid=grid, in_specs=[wide, wide, narrow], out_specs=[wide, narrow],
        out_shape=[jax.ShapeDtypeStruct(ctx.shape, ctx.dtype),
                   jax.ShapeDtypeStruct(a.shape, jnp.float32)],
        compiler_params=_parallel(grid), interpret=interpret, name="head_gate_backward",
    )(d, ctx, a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gate_kernels(ctx, a, interpret=False):
    return _gate_call(ctx, a, interpret=interpret)


def _gate_kernels_fwd(ctx, a, interpret):
    return _gate_call(ctx, a, interpret=interpret), (ctx, a)


def _gate_kernels_bwd(interpret, kept, d):
    return tuple(_gate_back_call(d, *kept, interpret=interpret))


_gate_kernels.defvjp(_gate_kernels_fwd, _gate_kernels_bwd)


def gate_heads(ctx, a):
    """``ctx (batch, heads, positions, size)`` times ``a (batch, positions,
    heads)`` float32, a scalar a head and position, in ``ctx``'s type."""
    if a.dtype != jnp.float32:
        raise ValueError(f"the gate is float32, not {a.dtype}")
    if jax.default_backend() == "tpu" and _gate_rows(ctx):
        return _gate_kernels(ctx, a)
    return _gated(ctx, a)
