"""Dropless expert layer: a top-k router over all the experts of the model
(sigmoid scores with a selection bias, :func:`sigmoid_topk_route`, or a
softmax over the chosen logits, :func:`softmax_topk_route`), and the part of
the result that the experts *held here* give, each a gated unit whose gate is
the caller's (SiLU by default, ReLU in ``models/smallthinker_moe.py``) or, with
no gate, two products around the caller's activation (``models/nemotron_h.py``:
``down(relu(up u)^2)``).

The GShard layer beside it (:mod:`bagua_tpu.parallel.moe.layer`) sends every
token through a dense ``(tokens, experts, capacity)`` mask and drops what
exceeds the capacity.  This one sorts: the ``tokens x k`` assignments are
ordered by expert, the rows of the held experts come first, one grouped
matrix product per projection runs over exactly those rows, and each token
takes its rows back by the inverse permutation.  Nothing is dropped at any
load: a token chooses ``k`` *distinct* experts, so at most ``min(k, held)`` of
its choices are held here, and the row buffer has ``tokens x min(k, held)``
rows, which no routing can exceed; the grouped product skips what lies past
the held groups.  Where a token makes no more choices than the chip holds
experts (the first three callers below) that is ``tokens x k``, every
assignment's own row; where it makes more (22 of 512 with 8 held:
``tokens x 8`` and not ``tokens x 22``) the sort still runs over all ``tokens
x k`` keys, the buffer is the first rows of that order, and an assignment
whose place lies past it is dead like any other that is not held.

The layer is *told* which experts it holds (``held = (first, count)`` of
``num_experts``).  It routes over all of them, normalises the ``k`` weights
over all ``k`` chosen experts, and adds only the terms of the chosen experts
it holds: with every expert held that is the whole layer, with an eighth of
them it is this chip's share of an expert-parallel deployment, and the
shares of all chips add up to the whole (``tests/test_glm_moe.py``).  It has
no exchange of its own and no stand-in for one.

The permutation out and the permutation back are two passes that are each
other's transpose, and every use in the layer is one of them (PR 34):

* :func:`spread`: row ``r`` of the buffer takes ``scale[r] * src[perm[r] //
  k]`` where it is live and zero where it is not.  Dispatch forward is
  ``spread`` of ``x`` with no scale (scope ``moe_dispatch``); combine's
  backward is ``spread`` of the token's gradient with the row's weight as
  scale, and the weights' gradient, a row-wise dot product with the saved
  buffer, rides beside it (``moe_combine``).
* :func:`collect`: token ``t`` takes ``sum_j w[t, j] * rows[inverse[t * k +
  j]]`` over its live choices, accumulated in float32 and rounded once.
  Combine forward is ``collect`` with the router's weights
  (``moe_combine``); dispatch's backward is ``collect`` with weight 1 of the
  sum of the two input gradients (``moe_dispatch``).

Each is a ``jax.custom_vjp`` whose backward is the other, so autodiff builds
neither a ``(tokens, k, hidden)`` array (on the chip a re-tiling to four
sublanes), nor its float32 broadcast, nor a scatter-add.  A dead row holds
whatever the buffer held: the passes *select* it away and never scale it.
Arrays are sized to the most rows that can be live, never to those that are;
one thing is branched on them: where a token makes more choices than experts
are held, ``collect`` first brings each token's live choices to the front of
its ``k`` (a sort of ``k`` keys a token) and gathers ``min(k, held)`` times,
not ``k`` times, and it takes the rows from the buffer's first ``tokens`` rows
alone when no more are live, which holds whenever the load is near its
expectation: 16 MB, a source that fits fast memory, where the whole buffer is
134 MB (by a plain SGD step's time of one such layer on the chip, 67.81 ms
with 22 gathers, 61.56 with 8, 59.09 with 8 from the first rows; ``PERF.md``
section 6, PR 45).  The scalars a
pass needs in the other order (the weight a row, the gradient a choice) are
reordered by a sort, which costs a tenth of the gather.

The grouped product is ``megablox.gmm`` (Pallas, ships with JAX) on a TPU
and ``jax.lax.ragged_dot`` elsewhere; rows past the held groups are left
unwritten by the one and zero by the other, so every use masks them.  The
kernel's tile is a function of the product's shape (:func:`gmm_tiling`): the
layer has six callers whose experts differ in width, in the model's hidden
size, in the choices a token makes, in the groups held and in the rows a group
gets (``models/glm_moe.py`` 1536 of 2048, 4 choices; ``models/lfm2_moe.py``
1792 of 2048, 4; ``models/smallthinker_moe.py`` 768 of 2560, 6: a buffer of
49,152 rows; ``models/nemotron_h.py`` 2688 of a latent 1024, no gate, 22
choices of 512 experts with 8 held: a buffer of 65,536 rows for 2,816
expected; ``models/laguna.py`` 512 of 2048, 8 choices of 256 experts with 32
held, the one caller with more than 8 groups: a buffer of 65,536 rows for
8,192 expected, 256 a group; ``models/solar_open2.py`` 1280 of 4096, the widest
rows yet, 8 choices of 320 experts with 8 held: a buffer of 65,536 rows for
1,638 expected, 205 a group, 537 MB a copy in bf16).
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from bagua_tpu.observability.annotations import model_scope

#: (rows, contraction, columns) tiles of the grouped product measured on the
#: v5e by the step's time, by the experts' width, or by the product's own
#: ``(contraction, columns)`` where the two orientations want two tiles; 8
#: groups; a contraction of 0 is the whole of it in one tile.  1536
#: (``glm-4.7-flash``: 512 expected rows a group of 32,768; PERF.md, PR 29).
#: 1792 (``lfm2-8b-a1b``: 1,024 expected rows a group; 768 does not divide
#: 1792 = 2 x 7 x 128; PERF.md section 6, PR 33, with the multi-query
#: attention kernels: 128 rows against the whole contraction 151.93 ms,
#: ``(256, 1024, 896)`` 156.73, ``(512, 1024, 896)`` 157.92, ``(128, 1024,
#: 896)`` 159.87, ``(256, 512, 896)`` 159.86; 256 rows against the whole
#: contraction, 1,024 rows, or 1,792 columns a tile are refused for the 16 MB
#: of fast memory).  768 of 2,560 (``smallthinker-21ba3b``: 768 expected rows
#: a group of 49,152; PERF.md section 6, PR 36, fourteen tiles by a plain SGD
#: step's time: these two 165.52 ms; the whole contraction against 384
#: columns, and 640 or 384 the other way, 165.88 to 167.13; ``(512, 1280,
#: 768)`` with 1,280 or 768 columns the other way 166.10 and 166.78; a
#: contraction of 512, which the default below gives, 167.78 to 170.35; 768
#: columns against the whole contraction are refused for fast memory).
#: 2,688 of a latent 1,024 (``nemotron-3-super``: 352 expected rows a group of
#: 65,536; PERF.md section 6, PR 45, ten tiles by a plain SGD step's time of
#: one expert layer: at these rows the tile moves nothing that can be read,
#: 61.53 to 61.58 ms over all ten, these two 61.53 and the default below, whose
#: contraction of 2,688 falls to tiles of 128, 61.58; none refused, 2,688
#: columns against the whole contraction among them).  512 of 2,048
#: (``laguna-xs.2``: 32 groups of 256 expected rows in a buffer of 65,536;
#: PERF.md section 6, PR 49, eight tiles by a plain SGD step's time of one
#: expert layer: the whole contraction and all columns in one tile both ways,
#: at 128 rows 60.53 ms and at 256 rows 60.54; 1,024 columns the other way
#: 60.67; a contraction of 1,024 or 512, or 512 rows, 61.23 to 61.47; the
#: default below 62.39; none refused).  1,280 of 4,096 (``solar-open2-250b``: 8
#: groups of 205 expected rows in a buffer of 65,536; PERF.md section 6, PR 52,
#: eight tile pairs by a plain SGD step's time of one expert layer under a GQA
#: mixer: 256 rows against a contraction of 1,024 and all 1,280 columns, the
#: whole contraction and 1,024 columns the other way, 81.91 ms, the same at 128
#: rows 81.96; 640 columns a tile, a contraction of 2,048 or 512, or 512 rows
#: 82.09 to 82.77; the default below 83.55; the whole contraction of 4,096
#: against 640 columns or more, and 2,048 columns the other way, are refused for
#: fast memory by the weights' gradient, whose float32 tile is contraction x
#: columns).  Every tile of it
#: divides its dimension: a contraction tile that hangs over is masked in
#: float32 at every step of the kernel's grid.
GMM_TILES = {1536: (512, 1024, 768), 1792: (128, 0, 896),
             (2560, 768): (256, 1280, 768), (768, 2560): (256, 0, 1280),
             (1024, 2688): (128, 0, 896), (2688, 1024): (128, 896, 1024),
             (2048, 512): (128, 0, 512), (512, 2048): (128, 0, 2048),
             (4096, 1280): (256, 1024, 1280), (1280, 4096): (256, 0, 1024)}


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """The tile of one grouped product ``(m, k) x (groups, k, n)`` on the
    chip; ``megablox`` asks it of the forward product and of the two products
    of its backward pass, each with its own ``k`` and ``n``.  Of an expert's
    two dimensions the narrower is its width and the other the model's hidden
    size: a measured shape or width takes its tile from :data:`GMM_TILES`
    (a width whichever of ``k`` and ``n`` it is); any other takes half the
    width across, or one tile of 128 lanes where that is no multiple of them,
    against the most of the contraction, up to 1,024, that divides it in
    whole tiles of lanes (the whole of it where nothing does)."""
    width = min(k, n)
    measured = GMM_TILES.get((k, n)) or GMM_TILES.get(width)
    if measured:
        rows, contraction, columns = measured
        return rows, contraction or k, columns
    half = width // 2
    contraction = next((c for c in (1024, 512, 256, 128) if k % c == 0), k)
    return 512, contraction, half if half % 128 == 0 else 128


def _of_chosen(values, chosen):
    """``values[t, chosen[t, j]]`` by a select over the experts: a gather of
    tokens x k scalars takes 0.33 ms a layer on the chip, this 0.01 (PERF.md,
    PR 34)."""
    picked = chosen[..., None] == jnp.arange(values.shape[-1])
    return jnp.sum(jnp.where(picked, values[:, None, :], 0), axis=-1)


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
    weights = _of_chosen(scores, chosen)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen.astype(jnp.int32), weights * scaling


def softmax_topk_route(h, router_kernel, k: int) -> Tuple[jax.Array, jax.Array]:
    """``(chosen experts (tokens, k) int32, their weights (tokens, k) f32)``.

    In float32 at the highest matmul precision, whatever ``h`` came in: ``l
    = h W_r``; the ``k`` experts of largest ``l`` (among equals the lower
    index, as ``lax.top_k`` has it); ``w = softmax(l[chosen])`` over those
    ``k`` alone, so the weights sum to one with nothing more to divide by.
    No selection bias and no scaling factor."""
    logits = jnp.dot(h.astype(jnp.float32), router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, chosen = jax.lax.top_k(logits, k)
    return chosen.astype(jnp.int32), jax.nn.softmax(_of_chosen(logits, chosen), axis=-1)


# -- the two passes ------------------------------------------------------------
#
# ``order = (perm, inverse, n_live)`` describes the row buffer: ``perm`` a
# permutation of the ``tokens x k`` assignments (row ``r`` belongs to token
# ``perm[r] // k``), ``inverse`` its inverse (choice ``j`` of token ``t`` lies
# in row ``inverse[t * k + j]``), and the rows from 0 to ``n_live - 1`` live.
# A buffer *bounded* below ``tokens x k`` rows has the first ``rows`` entries
# of ``perm`` and the whole of ``inverse``: a choice whose place lies past the
# buffer is past ``n_live`` too, and dead.


def _live(rows: int, n_live):
    return jnp.arange(rows) < n_live


def _reorder(values, index, index_inverse):
    """``values[index]`` of one scalar a row, for a permutation ``index``
    with inverse ``index_inverse``, as a sort by ``index_inverse``: 0.02 ms
    for 32,768 scalars on the chip, where the gather takes 0.25."""
    return jax.lax.sort((index_inverse, values), num_keys=1)[1]


def _bounded(order) -> bool:
    return order[0].shape[0] < order[1].shape[0]


def _to_rows(by_choice, order):
    """One scalar a choice as one a row of the buffer."""
    perm, inverse, _ = order
    by_row = _reorder(by_choice, perm, inverse)
    return by_row[:perm.shape[0]] if _bounded(order) else by_row


def _to_choices(by_row, order):
    """One scalar a row of the buffer as one a choice; a choice whose place
    lies past a bounded buffer takes zero."""
    perm, inverse, _ = order
    if _bounded(order):
        # the whole permutation again, from its inverse
        by_row = jnp.pad(by_row, (0, inverse.shape[0] - perm.shape[0]))
        perm = _reorder(jnp.arange(inverse.shape[0], dtype=inverse.dtype), perm, inverse)
    return _reorder(by_row, inverse, perm)


def _row_dots(buffer, src, order, fan: int):
    """``dot(buffer[r], src[perm[r] // fan])`` of every live row in float32,
    in the buffer's order: the gradient of a row's scale."""
    perm, _, n_live = order
    dots = jnp.sum(buffer.astype(jnp.float32) * src[perm // fan].astype(jnp.float32), axis=-1)
    return jnp.where(_live(perm.shape[0], n_live), dots, 0)


#: rows of zeros put under ``spread``'s source for the dead rows to take: a
#: tile of bf16
PAD_ROWS = 16


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def spread(src, scale, order, fan: int):
    """Row ``r`` of the buffer (``rows(src) * fan`` rows, or as many as it
    is bounded to) takes ``scale[r] * src[perm[r] // fan]``, rounded once
    (``src[perm[r] // fan]`` where ``scale`` is ``None``), where ``r <
    n_live``, and zero where not.  One gather from the ``rows(src)`` rows;
    the transpose of :func:`collect`.

    Without a scale the select is in the index: a dead row takes a row of
    zeros put under ``src``, and the gather is the whole pass.  With one, the
    product and the select fuse into the pass that reads the buffer next
    (by the step's time on the chip, PERF.md section 6, PR 34)."""
    perm, _, n_live = order
    live = _live(perm.shape[0], n_live)
    if scale is None:
        padded = jnp.pad(src, ((0, PAD_ROWS), (0, 0)))
        return padded[jnp.where(live, perm // fan, src.shape[0])]
    return jnp.where(live[:, None], (scale[:, None] * src[perm // fan]).astype(src.dtype), 0)


def _spread_fwd(src, scale, order, fan):
    return spread(src, scale, order, fan), (None if scale is None else src, scale, order)


def _spread_bwd(fan, res, grad):
    src, scale, order = res
    if scale is None:
        return collect(grad, None, order, fan), None, None
    weight = _to_choices(scale, order).reshape(-1, fan)
    return collect(grad, weight, order, fan), _row_dots(grad, src, order, fan), None


spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def collect(buffer, weight, order, fan: int):
    """Token ``t`` takes ``sum_j weight[t, j] * buffer[inverse[t * fan + j]]``
    (weight 1 where ``weight`` is ``None``) over the ``j`` whose row is live
    (``inverse[t * fan + j] < n_live``), accumulated in float32 and rounded
    once.  A dead row is selected away, never scaled: it holds whatever the
    buffer held.  The transpose of :func:`spread`.

    ``fan`` gathers of ``rows(buffer) / fan`` rows and their sum, never an
    array whose second-minor dimension is ``fan``.  The result is written
    here (the barrier): left to fuse into its readers, the sum is formed
    once in the forward and once in the backward pass, and the ``fan``
    gathered arrays of every layer stay live between the two."""
    _, inverse, n_live = order
    index = inverse.reshape(-1, fan)
    if _bounded(order):
        # a token has at most rows(buffer) / tokens live choices: they come to
        # the front of its ``fan``, a dead one's key past every row
        fan = buffer.shape[0] // index.shape[0]
        keyed = jnp.where(index < n_live, index, inverse.shape[0])
        if weight is None:
            index = jax.lax.sort(keyed, dimension=1)[:, :fan]
        else:
            index, weight = (
                a[:, :fan] for a in jax.lax.sort((keyed, weight), dimension=1, num_keys=1))

    def gathered(rows, at):
        total = 0.0
        for j in range(fan):
            taken = rows[at[:, j]].astype(jnp.float32)
            if weight is not None:
                taken = weight[:, j, None] * taken
            total = total + jnp.where((index[:, j] < n_live)[:, None], taken, 0)
        return total.astype(buffer.dtype)

    if not _bounded(order):
        total = gathered(buffer, index)
    else:
        # what points past the rows read is clamped and selected away.  Live
        # rows lie first: where no more than ``tokens`` are live, which holds
        # whenever the load is near its expectation, the gathers read the
        # buffer's first ``tokens`` rows alone, a source that fits fast memory
        def from_first(count):
            return lambda: gathered(buffer[:count], jnp.minimum(index, count - 1))

        tokens, rows = index.shape[0], buffer.shape[0]
        total = from_first(rows)() if rows <= tokens else jax.lax.cond(
            n_live <= tokens, from_first(tokens), from_first(rows))
    return jax.lax.optimization_barrier(total)


def _collect_fwd(buffer, weight, order, fan):
    return collect(buffer, weight, order, fan), (None if weight is None else buffer, weight, order)


def _collect_bwd(fan, res, grad):
    buffer, weight, order = res
    if weight is None:
        return spread(grad, None, order, fan), None, None
    scale = _to_rows(weight.reshape(-1), order)
    dots = _to_choices(_row_dots(buffer, grad, order, fan), order)
    return spread(grad, scale, order, fan), dots.reshape(weight.shape).astype(weight.dtype), None


collect.defvjp(_collect_fwd, _collect_bwd)


@jax.custom_vjp
def _for_two_readers(buffer):
    """``buffer`` twice: its two readers' gradients come back apart and are
    added here, under the caller's scope and not wherever autodiff would."""
    return buffer, buffer


_for_two_readers.defvjp(lambda buffer: ((buffer, buffer), None),
                        lambda _, grads: (grads[0] + grads[1],))


def grouped_matmul(rows, kernels, group_sizes):
    """``rows[group g] @ kernels[g]`` for consecutive groups of
    ``group_sizes`` rows from row 0; rows past the last group come back
    unwritten (chip) or zero (elsewhere)."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(rows, kernels, group_sizes, rows.dtype, gmm_tiling)
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


def dropless_experts(x, chosen, weights, gate, up, down, held: Tuple[int, int],
                     num_experts: int, activation=jax.nn.silu):
    """The held experts' part of ``sum_j weights[:, j] * E_chosen[:, j](x)``,
    ``E(u) = down(activation(gate u) * up u)``, or ``down(activation(up u))``
    where ``gate`` is ``None``.

    ``x`` (tokens, hidden); ``chosen``/``weights`` from one of the routers
    above (a token's ``k`` choices distinct); ``gate``, ``up`` (held, hidden,
    width) and ``down`` (held, width, hidden) the kernels of experts
    ``held[0] .. held[0] + held[1] - 1`` of ``num_experts``; ``activation``
    the gate's, SiLU (SwiGLU) unless the caller's model has another, or the
    ungated unit's own.  The buffer has ``tokens x min(k, held[1])`` rows."""
    tokens, k = chosen.shape
    first, count = held
    rows = tokens * min(k, count)
    with model_scope("moe_dispatch"):
        # held experts get keys 0 .. count-1, so their rows sort to the front
        key = ((chosen - first) % num_experts).reshape(-1)
        perm = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(perm)
        if rows < tokens * k:
            perm = perm[:rows]
        sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0, dtype=jnp.int32)
        n_live = jnp.sum(sizes)
        order = (perm, inverse, n_live)
        live = _live(rows, n_live)[:, None]
        buffer = spread(x, None, order, k)
        if gate is not None:
            for_gate, for_up = _for_two_readers(buffer)
    with model_scope("moe_experts"):
        # each product's result is masked before anything reads it: on the
        # chip the rows past the held groups are whatever the buffer held
        def product(lhs, kernels):
            return jnp.where(live, grouped_matmul(lhs, kernels.astype(x.dtype), sizes), 0)

        if gate is None:
            hidden = activation(product(buffer, up))
        else:
            hidden = activation(product(for_gate, gate)) * product(for_up, up)
        out = product(hidden, down)
    with model_scope("moe_combine"):
        return collect(out, weights, order, k)
