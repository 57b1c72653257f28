"""Dropless expert layer: a sigmoid top-k router over all the experts of the
model, and the part of the result that the experts *held here* give.

The GShard layer beside it (:mod:`bagua_tpu.parallel.moe.layer`) sends every
token through a dense ``(tokens, experts, capacity)`` mask and drops what
exceeds the capacity.  This one sorts: the ``tokens x k`` assignments are
ordered by expert, the rows of the held experts come first, one grouped
matrix product per projection runs over exactly those rows, and each token
takes its rows back by the inverse permutation.  Nothing is dropped at any
load: the row buffer has ``tokens x k`` rows, which no routing can exceed,
and the grouped product skips what lies past the held groups.

The layer is *told* which experts it holds (``held = (first, count)`` of
``num_experts``).  It routes over all of them, normalises the ``k`` weights
over all ``k`` chosen experts, and adds only the terms of the chosen experts
it holds: with every expert held that is the whole layer, with an eighth of
them it is this chip's share of an expert-parallel deployment, and the
shares of all chips add up to the whole (``tests/test_glm_moe.py``).  It has
no exchange of its own and no stand-in for one.

The grouped product is ``megablox.gmm`` (Pallas, ships with JAX) on a TPU
and ``jax.lax.ragged_dot`` elsewhere; rows past the held groups are left
unwritten by the one and zero by the other, so every use masks them.  The
kernel's tile is a function of the product's shape (:func:`gmm_tiling`): the
layer has two callers whose experts differ in width and in the rows a group
gets (``models/glm_moe.py`` 1536, ``models/lfm2_moe.py`` 1792).
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.observability.annotations import model_scope

#: (rows, contraction, columns) tiles of the grouped product measured on the
#: v5e by the step's time, by the experts' width; 8 groups in 32,768 rows; a
#: contraction of 0 is the whole of it in one tile.  1536 (``glm-4.7-flash``:
#: 512 expected rows a group; PERF.md, PR 29).  1792 (``lfm2-8b-a1b``: 1,024
#: expected rows a group; 768 does not divide 1792 = 2 x 7 x 128; PERF.md
#: section 6, PR 33, with the multi-query attention kernels: 128 rows against
#: the whole contraction 151.93 ms, ``(256, 1024, 896)`` 156.73, ``(512,
#: 1024, 896)`` 157.92, ``(128, 1024, 896)`` 159.87, ``(256, 512, 896)``
#: 159.86; 256 rows against the whole contraction, 1,024 rows, or 1,792
#: columns a tile are refused for the 16 MB of fast memory)
GMM_TILES = {1536: (512, 1024, 768), 1792: (128, 0, 896)}


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """The tile of one grouped product ``(m, k) x (groups, k, n)`` on the
    chip; ``megablox`` asks it of the forward product and of the two products
    of its backward pass, each with its own ``k`` and ``n``.  Of an expert's
    two dimensions the narrower is its width and the other the model's hidden
    size: a measured width takes its tile from :data:`GMM_TILES` whichever of
    ``k`` and ``n`` it is; any other takes half the width across, or one tile
    of 128 lanes where that is no multiple of them."""
    width = min(k, n)
    half = width // 2
    rows, contraction, columns = GMM_TILES.get(
        width, (512, 1024, half if half % 128 == 0 else 128))
    return rows, contraction or k, columns


def sigmoid_topk_route(h, router_kernel, correction_bias, k: int, scaling: float,
                       normalize: bool = True, eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """``(chosen experts (tokens, k) int32, their weights (tokens, k) f32)``.

    In float32 at the highest matmul precision, whatever ``h`` came in:
    ``s = sigmoid(h W_r)``; the ``k`` experts of largest ``s + b`` (``b``
    steers the choice only and takes no gradient: the ``noaux_tc`` method);
    ``w = s[chosen] / (sum s[chosen] + eps) * scaling`` (``eps``: 1e-20 in
    the ``glm4_moe_lite`` family, 1e-6 in ``lfm2_moe``)."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(correction_bias), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen.astype(jnp.int32), weights * scaling


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, perm, inverse, fan: int):
    """``x[perm // fan]``, where ``perm`` is a permutation of
    ``rows(x) * fan`` positions with inverse ``inverse``.  Its transpose is a
    gather by ``inverse`` and a sum over ``fan``: autodiff's scatter-add of
    32,768 rows never appears."""
    return x[perm // fan]


def _take_rows_fwd(x, perm, inverse, fan):
    return x[perm // fan], (inverse, x.shape[0])


def _take_rows_bwd(fan, res, g):
    inverse, rows = res
    return g[inverse].reshape(rows, fan, g.shape[-1]).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def grouped_matmul(rows, kernels, group_sizes):
    """``rows[group g] @ kernels[g]`` for consecutive groups of
    ``group_sizes`` rows from row 0; rows past the last group come back
    unwritten (chip) or zero (elsewhere)."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, kernels, group_sizes, rows.dtype, gmm_tiling)
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


def dropless_experts(x, chosen, weights, gate, up, down, held: Tuple[int, int],
                     num_experts: int):
    """The held experts' part of ``sum_j weights[:, j] * E_chosen[:, j](x)``.

    ``x`` (tokens, hidden); ``chosen``/``weights`` from
    :func:`sigmoid_topk_route`; ``gate``, ``up`` (held, hidden, width) and
    ``down`` (held, width, hidden) the SwiGLU kernels of experts
    ``held[0] .. held[0] + held[1] - 1`` of ``num_experts``."""
    tokens, k = chosen.shape
    first, count = held
    with model_scope("moe_dispatch"):
        # held experts get keys 0 .. count-1, so their rows sort to the front
        key = ((chosen - first) % num_experts).reshape(-1)
        perm = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(perm)
        sizes = jnp.bincount(key, length=num_experts)[:count].astype(jnp.int32)
        live = (jnp.arange(tokens * k) < jnp.sum(sizes))[:, None]
        rows = jnp.where(live, _take_rows(x, perm, inverse, k), 0)
    with model_scope("moe_experts"):
        # each product's result is masked before anything reads it: on the
        # chip the rows past the held groups are whatever the buffer held
        def product(lhs, kernels):
            return jnp.where(live, grouped_matmul(lhs, kernels.astype(x.dtype), sizes), 0)

        hidden = jax.nn.silu(product(rows, gate)) * product(rows, up)
        out = product(hidden, down)
    with model_scope("moe_combine"):
        mine = ((chosen >= first) & (chosen < first + count))[..., None]
        back = _take_rows(out, inverse, perm, 1).reshape(tokens, k, -1)
        share = jnp.where(mine, weights[..., None], 0.0)
        return jnp.sum(jnp.where(mine, back, 0) * share, axis=1).astype(x.dtype)
