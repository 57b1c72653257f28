"""The one token-embedding lookup of the three expert models
(``models/glm_moe.py``, ``models/lfm2_moe.py``, ``models/smallthinker_moe.py``).

Forward it is the gather it always was, ``table[ids]`` rounded to the compute
dtype.  What is written here is its gradient.  Autodiff makes the gradient of
a gather of rows a row scatter-add, and on the chip that is one fusion that
reads, adds and writes the table's rows one after another: 8.41 ms a step
for 8,192 rows of 2,560 columns (``fusion.237 f32[18992,2560]``, the longest
single operation of ``smallthinker-21ba3b.dp1-s8192``; ledger, PR 39) and
1.54 ms for 8,192 rows of 2,048, from one and the same compiled program
(``PERF.md`` section 6, PR 41).

On a TPU the table's gradient is a grouped product instead
(:func:`grouped_table_gradient`): the tokens sorted by id fall into
consecutive groups, one per block of ``block`` vocabulary rows, and a block's
gradient is ``onehot(id mod block)^T @ g`` over its group, which is
``megablox.tgmm`` (Pallas, ships with JAX), the kernel
``parallel/moe/dropless.py`` drives for the experts' weight gradients.  The
one-hot is exact in any float type, the products are exact and the sums are
float32, as the scatter's are; only the order of the float32 additions
differs.  Elsewhere the gradient is the scatter-add it was, by backend and
with no option, as ``dropless.grouped_matmul`` chooses its kernel.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.observability.annotations import model_scope


def grouped_tiling(hidden: int) -> Tuple[int, int, int]:
    """``(token tile, block, column tile)`` of the grouped product for a table
    of ``hidden`` columns, ``tgmm``'s tiles with a block of vocabulary rows a
    tile: 256 tokens, 256 vocabulary rows, and the most columns up to 1,280
    that divide ``hidden`` in whole tiles of 128 lanes (all of it where
    nothing does).

    The lookup's backward pass and update alone, 8,192 ids, on a v5e (my chip
    run, PR 41; ms): the scatter-add 9.89 at ``[18992, 2560]``, 2.00 at
    ``[16384, 2048]``, 2.26 at ``[19360, 2048]``; this tiling 1.92, 1.35 and
    1.09; blocks of 128 within 0.02 of it, blocks of 512 with tiles of 512
    2.05, 1.45 and 1.19, blocks of 1,024 and token tiles of 1,024 slower
    still.  One id 2,000 times over moves neither form by 0.01."""
    columns = next((c for c in range(1280, 0, -128) if hidden % c == 0), hidden)
    return 256, 256, columns


def _inside_or_vocab(ids, vocab: int):
    """``ids`` with every id outside ``[0, vocab)`` made ``vocab``, the row
    past the table's last, which no gradient has."""
    return jnp.where((ids >= 0) & (ids < vocab), ids, vocab)


def grouped_table_gradient(g, ids, vocab: int, tiles: Tuple[int, int, int],
                           interpret: bool = False):
    """``zeros((vocab, hidden), f32).at[ids].add(g)`` for ``g (tokens,
    hidden)`` and ``ids (tokens,)`` as one grouped product, ``tiles`` as
    :func:`grouped_tiling` gives them: ids sorted with their positions as
    payload, ``g``'s rows gathered into that order, the rows of each block of
    ``tiles[1]`` vocabulary rows counted by compare and sum, and
    ``tgmm(onehot(id mod block)^T, g_sorted, sizes)``, which visits an empty
    block to write its zeros.  An id outside ``[0, vocab)`` counts as row
    ``vocab``: it sorts past every row of the table, into the last block's
    rows that the result leaves out or into no block; so do the rows that
    fill ``tokens`` up to whole token tiles."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    tokens, hidden = g.shape
    block = tiles[1]
    blocks = -(-vocab // block)
    fill = -tokens % tiles[0]
    key = jnp.pad(_inside_or_vocab(ids, vocab), (0, fill), constant_values=vocab)
    key, position = jax.lax.sort((key, jnp.arange(tokens + fill, dtype=jnp.int32)), num_keys=1)
    g_sorted = jnp.pad(g, ((0, fill), (0, 0)))[position]
    sizes = jnp.sum((key // block)[:, None] == jnp.arange(blocks), axis=0, dtype=jnp.int32)
    onehot = ((key % block)[:, None] == jnp.arange(block)).astype(g.dtype)
    out = tgmm(onehot.T, g_sorted, sizes, jnp.float32, tiles, interpret=interpret)
    return out.reshape(blocks * block, hidden)[:vocab]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows(table, ids, dtype):
    return table[ids].astype(dtype)


def _rows_fwd(table, ids, dtype):
    # of the table the backward pass reads the shape and the dtype alone
    return _rows(table, ids, dtype), (table, ids)


def _rows_bwd(dtype, res, g):
    table, ids = res
    vocab, hidden = table.shape
    g, ids = g.reshape(-1, hidden), ids.reshape(-1)
    if jax.default_backend() == "tpu":
        grad = grouped_table_gradient(g, ids, vocab, grouped_tiling(hidden))
    else:
        grad = jnp.zeros((vocab, hidden), jnp.float32).at[_inside_or_vocab(ids, vocab)].add(
            g.astype(jnp.float32), mode="drop")
    return grad.astype(table.dtype), None


_rows.defvjp(_rows_fwd, _rows_bwd)


def embed(table, ids, dtype):
    """``table[ids]`` in ``dtype``, under ``bagua_model/part=embed``:
    ``table (vocab, hidden)`` as stored (float32), ``ids`` integers of any
    shape in ``[0, vocab)``; the result has ``ids``' shape and a last axis of
    ``hidden``.  The table's cotangent has the table's shape and dtype and
    sums the repeats of an id in float32, so a table that is the output
    matrix too takes the sum of its two gradients as any leaf does.

    An id outside ``[0, vocab)``: forward, whatever ``jnp`` indexing gives
    (a negative id counts from the end, one past the end reads the last row
    on the backends here); backward it picks nothing: its cotangent is added
    to no row, where the gather's own gradient would add it to the row it
    read."""
    with model_scope("embed"):
        return _rows(table, ids, dtype)
