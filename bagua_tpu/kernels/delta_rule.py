"""The gated delta rule of a Kimi Delta Attention (KDA) layer in its chunked form.

The recurrence, for one head with a state ``S`` of ``key size x value size``
that starts at zero, ``alpha_t = exp(g_t)`` a *vector* of one decay a key
channel, ``beta_t`` a scalar:

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            o_t = S_t^T q_t

that is ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T``: the state forgets channel by channel, and what it holds under ``k_t``
is replaced by ``v_t`` (the delta rule) and not added to.  ``ssd_scan.py``'s
recurrence is the case of one decay a head and no correction; neither of its
two properties carries over.

**The chunk's algebra** (the WY/UT form of the delta rule).  Cut the positions
into chunks of ``C``; inside a chunk let ``G_i`` be the running sum of ``g`` to
and with position ``i`` (a vector, falling), ``S_0`` the state at the chunk's
start, and ``u~_j = beta_j (v_j - S'_j^T k_j)`` the corrected value position
``j`` writes.  With the decayed scores

    A_jl = sum_c k_j[c] k_l[c] exp(G_j[c] - G_l[c])    (l < j)
    B_ij = sum_c q_i[c] k_l[c] exp(G_i[c] - G_j[c])    (j <= i)

the corrections of one chunk solve a unit lower-triangular system, ``(I +
Diag(beta) A) U~ = Diag(beta) (V - (K exp(G)) S_0)``.  So with ``[W | Y] = (I +
Diag(beta) A)^-1 Diag(beta) [V | K exp(G)]``, which need no state,

    U~ = W - Y S_0
    O  = (Q exp(G)) S_0 + B U~
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U~

and only the last line runs from chunk to chunk.

**Every exponent is at most zero.**  The decay between two positions differs
by channel, so it cannot multiply a score after the product; and ``k *
exp(-G)`` against ``q * exp(G)``, the split that one decay a head allows,
overflows float32 at decays the layer's ``A_log`` and ``dt_bias`` reach (a
``g`` of -2 over 64 positions is ``exp(128)``).  So a chunk is cut into
``SUB_BLOCKS`` sub-blocks.  A score between two *different* sub-blocks is
split around a reference position between the two, the first position ``r`` of
the later one: ``exp(G_i - G_r) exp(G_r - G_j)``, each factor at most one,
folded into ``q`` or ``k`` on its side of the contraction.  A score inside one
sub-block has no position between its two, and is taken channel by channel:
``sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])`` with the exponent masked to
``-inf`` above the diagonal.  What decays to nothing underflows to zero, as it
should.  ``tests/test_delta_rule.py`` feeds decays at which ``exp(-G)`` over
one chunk is infinite.

Operands of the matrix products are in ``v``'s type (bf16 in the benchmark)
with float32 accumulation; ``g``, ``beta``, the running sums, the decays, the
triangular solve and the carried state are float32.

Two implementations of that arithmetic, one per backend, chosen by
``jax.default_backend()`` and the shapes and by no option:

* :func:`_chunked`, in plain ``jax.numpy``: the scores and the solve for all
  chunks at once, the carried state by a ``lax.scan`` over the chunks that does
  two products a chunk and keeps the state at each chunk's start, from which
  the outputs of all chunks are one batched product afterwards; the backward
  pass is autodiff's.  It runs on every backend but the TPU, and on the TPU for
  shapes the kernels do not take; it is what the kernels are tested against.
  On the chip it was 45.4 ms of a 262 ms step, 15 ms a layer at the benchmark's
  share (8,192 positions, 8 heads of 128 by 128), 1.6% of the rule's roofline:
  the channel-by-channel scores and their cotangents 4.4 ms a layer, the
  batched solves 2.5, three scans of 128 steps 1.9 (``PERF.md`` section 6,
  PR 53).
* on a TPU, a pair of Pallas kernels under one ``jax.custom_vjp``
  (:func:`_rule_kernels`).  A grid step is one chunk of two heads (of one
  where the heads do not pair off); the grid is ``(sequences, chunks, heads /
  2)``, chunks and heads sequential and the heads innermost, and the state of
  every head, ``(value size, key size)`` float32, is carried in scratch from
  chunk to chunk, ``S <- Diag(exp(G_C)) S + (K exp(G_C - G))^T U~``: the
  recurrence itself, never rounded between chunks.  The running sum (shift
  and add), the decays, both kinds of scores (inside a sub-block a diagonal
  at a time: position ``i`` against ``i - d``, a shift of ``d`` positions),
  the inverse of ``I + Diag(beta) A`` and ``W``, ``Y`` are built in fast
  memory.  The inverse is a substitution by blocks, doubled: the inverse of
  ``[[P, 0], [C, Q]]`` is that of ``P`` and of ``Q`` with ``-Q^-1 C P^-1`` in
  the corner, so from blocks of one position to the whole chunk a level is
  two products of entries of the inverse itself, float32 at the highest
  precision (a finite series in the system's powers, exact on paper, lost
  every digit but one at keys that repeat under ``beta`` near 2).  These ten
  products of 64 by 64 are half of the forward kernel's time.  Two heads a
  step are fewer steps and fewer reads of ``beta``, no more: the chip read
  5.08, 4.89, 4.81 and 4.72 ms a layer for both kernels at one, two, four
  and eight heads a step, and a body written out head by head is that much
  slower to trace.  The forward kernel keeps for the backward one, a chunk
  and head, the state at its start (128 x 8 x 128 x 128 float32, 67.1 MB a
  layer at that share), the scores and the inverse, ``[A | B | inverse]``,
  beside the five operands; the backward kernel walks the chunks from the last to the first
  with the state's cotangent in scratch, builds the decays, ``W`` and ``Y``
  again, and writes ``dq``, ``dk``, ``dv``, ``dg`` and ``dbeta`` itself.
  ``dg`` is a sum of flows between positions that cancels on paper; every
  flow is formed once and both its ends take the same number.  The kernels
  take positions in whole chunks of 64 and key and value sizes that are
  multiples of 128.  What the caller keeps is its own to decide
  (``models/solar_open2.py`` puts the layer's core under one
  ``jax.checkpoint``: the forward kernel runs again in the backward pass and
  what it keeps lives only there).
"""

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

from bagua_tpu.kernels.ssd_scan import _running

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a b
_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b

#: sub-blocks a chunk: scores inside one are taken channel by channel (16
#: positions at a chunk of 64)
SUB_BLOCKS = 4


def _masked_exp(gap, open_):
    """``exp(gap)`` where ``open_`` and zero where not, with no exponential of
    what the mask hides: there ``gap`` is positive and may overflow."""
    return jnp.exp(jnp.where(open_, gap, -jnp.inf))


def _chunk_scores(q, k, run, sub: int):
    """``(A, B)`` of every chunk: ``A_jl`` for ``l < j`` and ``B_ij`` for ``j
    <= i`` (zero elsewhere), float32 ``(..., chunk, chunk)``.  ``q``, ``k``
    ``(..., chunk, size)`` in the compute type, ``run`` the running sum of
    ``g`` inside the chunk, float32."""
    f32, dtype = jnp.float32, k.dtype
    chunk, size = k.shape[-2:]
    blocks = chunk // sub
    lead = k.shape[:-2]
    by_block = lead + (blocks, sub, size)
    qb, kb, rb = q.reshape(by_block), k.reshape(by_block), run.reshape(by_block)
    qf, kf = qb.astype(f32), kb.astype(f32)

    # inside a sub-block, channel by channel: position i reads j <= i under exp(G_i - G_j)
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    decay = _masked_exp(rb[..., :, None, :] - rb[..., None, :, :], (i >= j)[..., None])
    diag_a = jnp.sum(kf[..., :, None, :] * kf[..., None, :, :] * decay, axis=-1)
    diag_b = jnp.sum(qf[..., :, None, :] * kf[..., None, :, :] * decay, axis=-1)
    diag_a = jnp.where(i > j, diag_a, 0.0)

    # between sub-blocks, around the later block's first position r: rows carry
    # exp(G_i - G_r), columns exp(G_r - G_j), both at most one
    ref = rb[..., :, :1, :]  # (..., blocks, 1, size)
    rows = jnp.exp(rb - ref)
    q_rows, k_rows = (qf * rows).astype(dtype), (kf * rows).astype(dtype)
    at = jnp.arange(chunk)
    earlier = (at[None, :] < (jnp.arange(blocks) * sub)[:, None])[..., None]  # (blocks, chunk, 1)
    columns = _masked_exp(ref - run[..., None, :, :], earlier)  # (..., blocks, chunk, size)
    k_columns = (k.astype(f32)[..., None, :, :] * columns).astype(dtype)
    off_a = jnp.einsum("...nic,...njc->...nij", k_rows, k_columns, preferred_element_type=f32)
    off_b = jnp.einsum("...nic,...njc->...nij", q_rows, k_columns, preferred_element_type=f32)

    def whole(diag, off):
        # the diagonal blocks set into the rows of their own sub-block
        placed = jnp.einsum("...nij,nm->...nimj", diag, jnp.eye(blocks, dtype=f32))
        return (off + placed.reshape(lead + (blocks, sub, chunk))).reshape(lead + (chunk, chunk))

    return whole(diag_a, off_a), whole(diag_b, off_b)


def _chunked(q, k, v, g, beta, chunk: int):
    f32, dtype = jnp.float32, v.dtype
    batch, t, heads, size = k.shape
    width = v.shape[-1]
    n = t // chunk
    sub = chunk // SUB_BLOCKS if chunk % SUB_BLOCKS == 0 else chunk

    def by_chunk(x):  # (batch, heads, chunks, chunk, ...)
        x = x.reshape((batch, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    beta = by_chunk(beta.astype(f32))[..., None]  # (batch, heads, n, chunk, 1)
    run = jnp.cumsum(by_chunk(g.astype(f32)), axis=3)  # to and with a position
    total = run[..., -1:, :]
    kf = k.astype(f32)
    q_in = (q.astype(f32) * jnp.exp(run)).astype(dtype)  # reads the carried state
    k_in = kf * jnp.exp(run)  # what the carried state gives under k
    k_out = (kf * jnp.exp(total - run)).astype(dtype)  # writes the chunk's end state

    a, b = _chunk_scores(q, k, run, sub)
    system = jnp.eye(chunk, dtype=f32) + beta * a
    solved = solve_triangular(
        system, beta * jnp.concatenate([v.astype(f32), k_in], axis=-1),
        lower=True, unit_diagonal=True)
    w, y = solved[..., :width], solved[..., width:].astype(dtype)

    def step(state, of_chunk):
        w_n, y_n, k_n, decay = of_chunk
        written = w_n - jnp.einsum("bhik,bhkv->bhiv", y_n, state.astype(dtype),
                                   preferred_element_type=f32)
        after = decay[..., None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", k_n, written.astype(dtype), preferred_element_type=f32)
        return after, state

    _, starts = jax.lax.scan(
        step, jnp.zeros((batch, heads, size, width), f32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (w, y, k_out, jnp.exp(total[..., 0, :]))))
    starts = jnp.moveaxis(starts, 0, 2).astype(dtype)  # (batch, heads, n, size, width)
    written = w - jnp.einsum("bhnik,bhnkv->bhniv", y, starts, preferred_element_type=f32)
    out = (jnp.einsum("bhnik,bhnkv->bhniv", q_in, starts, preferred_element_type=f32)
           + jnp.einsum("bhnij,bhnjv->bhniv", b.astype(dtype), written.astype(dtype),
                        preferred_element_type=f32))
    return jnp.moveaxis(out, 1, 3).reshape(batch, t, heads, width).astype(dtype)


# -- the same arithmetic as a pair of Pallas kernels -------------------------------
#
# A grid step is one chunk of a few heads of one sequence; the grid is (sequences, chunks,
# heads a few at a time), chunks and heads sequential, the heads innermost so that a chunk's
# ``beta``, one column a head, is read once and its cotangent written once.  The state is
# held transposed, ``(value size, key size)`` float32 a head: the decay over a chunk, one
# number a key channel, multiplies it as a row, and every product that reads or writes it is
# plain, ``a b^T`` or ``a^T b``.  A chunk's terms are built in fast memory by
# :func:`_chunk_terms`, in both kernels; the backward one is handed the scores and the
# inverse the forward one built.


def _kernel_takes(k, v, chunk: int) -> bool:
    """Whether the shapes are ones the kernels take: chunks of 64 (sub-blocks
    of 16, whole tiles of either type), keys and values whole lane tiles."""
    return chunk == 64 and k.shape[3] % _LANES == 0 and v.shape[3] % _LANES == 0


def _product(a, b, dims=_NN, precision=None):
    """A product accumulated in float32: of operands in the compute type, at
    the default precision."""
    return jax.lax.dot_general(a, b, dims, precision=precision, preferred_element_type=jnp.float32)


#: a float32 product at the highest precision: the solve's
_exact = functools.partial(_product, precision=_HIGHEST)


def _rows_of(strips, sub: int):
    """``(chunk, n)`` from the strips of sub-blocks 1, 2, ...; sub-block 0's rows zero."""
    return jnp.concatenate([jnp.zeros((sub,) + strips[0].shape[1:], strips[0].dtype)] + strips, axis=0)


def _between(qf, kf, run, n: int, sub: int, dtype):
    """What the scores of sub-block ``n``'s rows against the earlier
    sub-blocks' columns are made of, around the sub-block's first position:
    the rows' decay ``(sub, size)`` and the columns' ``(chunk, size)``, zero
    from the sub-block on, and ``[k; q]`` of the rows and ``k`` of the columns
    under them, rounded."""
    rows = slice(n * sub, (n + 1) * sub)
    ref = run[n * sub:n * sub + 1]
    at = jax.lax.broadcasted_iota(jnp.int32, (run.shape[0], 1), 0)
    rows_decay = jnp.exp(run[rows] - ref)
    columns_decay = _masked_exp(ref - run, at < n * sub)
    rows_kq = jnp.concatenate([kf[rows] * rows_decay, qf[rows] * rows_decay], axis=0).astype(dtype)
    return rows_decay, columns_decay, rows_kq, (kf * columns_decay).astype(dtype)


def _sub_block(index, sub: int):
    """The sub-block a position lies in."""
    return sum((index >= n * sub).astype(jnp.int32) for n in range(1, SUB_BLOCKS))


def _within(run, d: int, sub: int):
    """``exp(G_i - G_{i-d})`` where position ``i - d`` lies in ``i``'s
    sub-block and zero where not, and a shift of ``d`` positions down."""
    at = jax.lax.broadcasted_iota(jnp.int32, (run.shape[0], 1), 0)
    inside = at - sub * _sub_block(at, sub) >= d
    shift = (lambda x: x) if d == 0 else (lambda x: pltpu.roll(x, d, 0))
    return _masked_exp(run - shift(run), inside), shift


def _chunk_terms(q, k, v, g, beta, kept=None):
    """What both kernels build of a chunk: ``q``, ``k`` ``(chunk, key size)``
    and ``v (chunk, value size)`` in the compute type, ``g`` of ``k``'s shape
    and ``beta (chunk, 1)`` float32.  ``kept``: ``[A | B | inverse]``, the
    scores and the system's inverse as the forward kernel built them, not
    built again."""
    f32, dtype = jnp.float32, v.dtype
    chunk = k.shape[0]
    sub = chunk // SUB_BLOCKS
    run = _running(g, 0)  # to and with a position
    total = run[chunk - 1:chunk]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    from_start, to_end = jnp.exp(run), jnp.exp(total - run)
    k_in = kf * from_start
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    between = [_between(qf, kf, run, n, sub, dtype) for n in range(1, SUB_BLOCKS)]
    if kept is None:
        a, b, inverse = _scores_and_inverse(qf, kf, run, between, beta, row, col)
    else:
        a, b, inverse = (kept[:, n * chunk:(n + 1) * chunk] for n in range(3))
    return types.SimpleNamespace(
        run=run, total=total, from_start=from_start, to_end=to_end, qf=qf, kf=kf, vf=vf,
        q_in=(qf * from_start).astype(dtype), k_in=k_in, k_out=(kf * to_end).astype(dtype),
        a=a, b=b, between=between, inverse=inverse,
        w=_exact(inverse, beta * vf), y=_exact(inverse, beta * k_in), sub=sub, row=row, col=col)


def _scores_and_inverse(qf, kf, run, between, beta, row, col):
    """``A``, ``B`` and ``(I + Diag(beta) A)^-1``, each ``(chunk, chunk)``."""
    f32 = jnp.float32
    chunk = row.shape[0]
    sub = chunk // SUB_BLOCKS
    # inside a sub-block, channel by channel, a diagonal at a time: position i reads i - d
    a = jnp.zeros((chunk, chunk), f32)
    b = jnp.zeros((chunk, chunk), f32)
    for d in range(sub):
        decay, shift = _within(run, d, sub)
        k_d = shift(kf) * decay
        b = b + jnp.where(col == row - d, jnp.sum(qf * k_d, axis=1, keepdims=True), 0.0)
        if d:
            a = a + jnp.where(col == row - d, jnp.sum(kf * k_d, axis=1, keepdims=True), 0.0)
    # between sub-blocks, around the later one's first position
    strips = [_product(rows_kq, columns_k, _NT) for _, _, rows_kq, columns_k in between]
    a = a + _rows_of([s[:sub] for s in strips], sub)
    b = b + _rows_of([s[sub:] for s in strips], sub)

    # (I + Diag(beta) A)^-1 by blocks, doubled: the inverse of [[P, 0], [C, Q]] is that of its
    # diagonal blocks less Q^-1 C P^-1 in the corner, so from blocks of one position (the unit
    # diagonal) to the whole chunk every level is two products, and what they multiply are
    # entries of the inverse itself: a substitution by blocks, as stable as one by rows
    system = beta * a
    inverse = (row == col).astype(f32)
    half = 1
    while half < chunk:
        corner = ((row & -(2 * half)) == (col & -(2 * half))) & ((row & half) != 0) & ((col & half) == 0)
        corner = jnp.where(corner, system, 0.0)
        inverse = inverse - (corner if half == 1 else _exact(_exact(inverse, corner), inverse))
        half *= 2
    return a, b, inverse


def _column(beta_ref, head):
    """``(chunk, 1)``: the head's column of a ``(chunk, heads)`` block."""
    beta = beta_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    return jnp.sum(jnp.where(lane == head, beta, 0.0), axis=1, keepdims=True)


def _heads_of_step(per: int, states):
    """``(head, its key columns, its value columns)`` of a grid step's heads;
    ``states`` the scratch of every head's ``(value size, key size)``."""
    _, width, size = states.shape
    first = pl.program_id(2) * per
    return [(first + j, slice(j * size, (j + 1) * size), slice(j * width, (j + 1) * width))
            for j in range(per)]


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, per):
    states = rest[-1]  # (heads, value size, key size) float32, at the chunk's start
    dtype = v_ref.dtype
    for j, (head, keys, values) in enumerate(_heads_of_step(per, states)):
        @pl.when(pl.program_id(1) == 0)
        def _():
            states[head] = jnp.zeros(states.shape[1:], states.dtype)

        start = states[head]
        c = _chunk_terms(q_ref[:, keys], k_ref[:, keys], v_ref[:, values], g_ref[:, keys],
                         _column(beta_ref, head))
        if len(rest) > 1:  # kept for the backward pass
            rest[0][j], rest[1][j] = start, jnp.concatenate([c.a, c.b, c.inverse], axis=1)
        start_r = start.astype(dtype)
        written = (c.w - _product(c.y.astype(dtype), start_r, _NT)).astype(dtype)
        o_ref[:, values] = (_product(c.q_in, start_r, _NT)
                            + _product(c.b.astype(dtype), written)).astype(dtype)
        states[head] = jnp.exp(c.total) * start + _product(written, c.k_out, _TN)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, built_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstates, *, per):
    for j, (head, keys, values) in enumerate(_heads_of_step(per, dstates)):
        d_q, d_k, d_v, d_g, d_beta = _backward_of_head(
            q_ref[:, keys], k_ref[:, keys], v_ref[:, values], g_ref[:, keys], _column(beta_ref, head),
            starts_ref[j], built_ref[j], do_ref[:, values], dstates, head)
        dq_ref[:, keys], dk_ref[:, keys], dv_ref[:, values], dg_ref[:, keys] = d_q, d_k, d_v, d_g
        lane = jax.lax.broadcasted_iota(jnp.int32, dbeta_ref.shape, 1)
        dbeta_ref[...] = jnp.where(lane == head, d_beta, dbeta_ref[...])


def _backward_of_head(q, k, v, g, beta, start, built, do, dstates, head):
    """One chunk of one head, the chunks taken from the last to the first;
    ``dstates[head]`` is the cotangent of the state at the chunk's end.

    The cotangent of the running sum ``G`` is a sum of flows: whatever
    position ``i`` reads of position ``j`` under ``exp(G_i - G_j)`` gives its
    product with its cotangent to ``G_i`` and takes it from ``G_j``.  Every
    flow is formed once, from the numbers the forward pass multiplied (the
    rounded ones where it rounded), and both ends take the same float32
    number, so that the sum over a chunk's positions cancels as on paper."""
    f32, dtype = jnp.float32, v.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstates[head] = jnp.zeros(dstates.shape[1:], dstates.dtype)

    c = _chunk_terms(q, k, v, g, beta, built)
    chunk, sub, row, col = c.run.shape[0], c.sub, c.row, c.col
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    d_end = dstates[head]
    start_r, d_end_r = start.astype(dtype), d_end.astype(dtype)
    y_r = c.y.astype(dtype)
    written = (c.w - _product(y_r, start_r, _NT)).astype(dtype)
    over = jnp.exp(c.total)

    # the state pass: O = q_in S + B U~, S' = exp(G_C) S + k_out^T U~, U~ = W - Y S
    d_written = _product(c.b.astype(dtype), do, _TN) + _product(c.k_out, d_end_r, _NT)
    d_written_r = d_written.astype(dtype)
    dstates[head] = over * d_end + _product(do, c.q_in, _TN) - _product(d_written_r, y_r, _TN)
    d_q_in = _product(do, start_r)
    d_b = jnp.where(row >= col, _product(do, written, _NT), 0.0)
    d_y = -_product(d_written_r, start_r)
    d_k_out = _product(written, d_end_r)
    k_out = c.k_out.astype(f32)
    d_total = (jnp.sum(start * d_end, axis=0, keepdims=True) * over
               + jnp.sum(d_k_out * k_out, axis=0, keepdims=True))

    # the solve: [W | Y] = inverse (beta [v | k_in]), the system I + beta A
    d_rv = _exact(c.inverse, d_written, _TN)
    d_rk = _exact(c.inverse, d_y, _TN)
    d_system = jnp.where(row > col, -(_exact(d_rv, c.w, _NT) + _exact(d_rk, c.y, _NT)), 0.0)
    d_beta = (jnp.sum(d_system * c.a, axis=1, keepdims=True)
              + jnp.sum(d_rv * c.vf, axis=1, keepdims=True)
              + jnp.sum(d_rk * c.k_in, axis=1, keepdims=True))
    d_a = beta * d_system
    d_k_in = beta * d_rk

    # what reads and writes the carried state: flows from the chunk's start and to its end
    d_q = d_q_in * c.from_start
    d_k = d_k_in * c.from_start + d_k_out * c.to_end
    d_run = (d_q_in * c.q_in.astype(f32) + d_k_in * c.k_in - d_k_out * k_out
             + jnp.where(at == chunk - 1, d_total, 0.0))

    # the scores between sub-blocks
    d_a_r, d_b_r = d_a.astype(dtype), d_b.astype(dtype)
    rows_q, rows_k, rows_flow = [], [], []
    for n in range(1, SUB_BLOCKS):
        rows = slice(n * sub, (n + 1) * sub)
        rows_decay, columns_decay, rows_kq, columns_k = c.between[n - 1]
        d_scores = jnp.concatenate([d_a_r[rows], d_b_r[rows]], axis=0)
        d_rows = _product(d_scores, columns_k)
        d_columns = _product(d_scores, rows_kq, _TN)
        rows_k.append(d_rows[:sub] * rows_decay)
        rows_q.append(d_rows[sub:] * rows_decay)
        flow = d_rows * rows_kq.astype(f32)
        flow = flow[:sub] + flow[sub:]
        flow_columns = d_columns * columns_k.astype(f32)
        rows_flow.append(flow)
        d_k = d_k + d_columns * columns_decay
        # what the reference position carries: the rows' flows leave it, the columns' arrive
        d_ref = jnp.sum(flow_columns, axis=0, keepdims=True) - jnp.sum(flow, axis=0, keepdims=True)
        d_run = d_run - flow_columns + jnp.where(at == n * sub, d_ref, 0.0)
    d_q = d_q + _rows_of(rows_q, sub)
    d_k = d_k + _rows_of(rows_k, sub)
    d_run = d_run + _rows_of(rows_flow, sub)

    # the scores inside a sub-block, a diagonal at a time
    for d in range(sub):
        decay, shift = _within(c.run, d, sub)
        k_d = shift(c.kf) * decay
        on = col == row - d
        d_b_d = jnp.sum(jnp.where(on, d_b, 0.0), axis=1, keepdims=True)
        d_q = d_q + d_b_d * k_d
        if d == 0:
            d_k = d_k + d_b_d * c.qf
            continue
        d_a_d = jnp.sum(jnp.where(on, d_a, 0.0), axis=1, keepdims=True)
        reads = d_a_d * c.kf + d_b_d * c.qf
        flow = reads * k_d
        back = lambda x: pltpu.roll(x, chunk - d, 0)
        d_k = d_k + d_a_d * k_d + back(reads * decay)
        d_run = d_run + flow - back(flow)

    # G is g summed to and with a position: g at a position takes every later G's
    return (d_q.astype(dtype), d_k.astype(dtype), (beta * d_rv).astype(dtype),
            _running(d_run, 0, backwards=True), d_beta)


def _heads_a_step(heads: int) -> int:
    """Two heads a grid step where the heads pair off: half the steps, and a
    chunk's ``beta`` read once for both."""
    return 2 if heads % 2 == 0 else 1


def _specs(chunk: int, size: int, width: int, heads: int, per: int, at):
    """Block specifications of ``k``-like, ``v``-like, ``beta``-like operands
    and of what is kept a chunk and head (the state at its start, the scores
    ``[A | B]`` and the system's inverse beside them), ``per`` heads a step; ``at`` maps
    the grid's chunk index to the chunk."""
    k = pl.BlockSpec((None, chunk, per * size), lambda i, n, h: (i, at(n), h))
    v = pl.BlockSpec((None, chunk, per * width), lambda i, n, h: (i, at(n), h))
    beta = pl.BlockSpec((None, chunk, heads), lambda i, n, h: (i, at(n), 0))

    def kept(*block):
        return pl.BlockSpec((None, None, per) + block, lambda i, n, h: (i, at(n), h, 0, 0))

    return k, v, beta, [kept(width, size), kept(chunk, 3 * chunk)]


_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# The two calls are jitted on the kernels' own flat operands and nothing else, as
# ``ssd_scan.py``'s are and for its reasons: a model's mixers share one trace and one lowering
# of each kernel, and the caller's reshapes stay where the compiler folds them away.


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "keep"))
def _forward_call(q, k, v, g, beta, *, chunk, interpret, keep):
    batch, t, heads = beta.shape
    size, width, n = k.shape[2] // heads, v.shape[2] // heads, t // chunk
    per = _heads_a_step(heads)
    k_like, v_like, beta_like, kept_like = _specs(chunk, size, width, heads, per, lambda m: m)
    flat = jax.ShapeDtypeStruct(v.shape, v.dtype)
    kept = [jax.ShapeDtypeStruct((batch, n, heads) + block, jnp.float32)
            for block in ((width, size), (chunk, 3 * chunk))]
    return pl.pallas_call(
        functools.partial(_forward_kernel, per=per),
        grid=(batch, n, heads // per),
        in_specs=[k_like, k_like, v_like, k_like, beta_like],
        out_specs=[v_like] + kept_like if keep else [v_like],
        out_shape=[flat] + kept if keep else [flat],
        scratch_shapes=[pltpu.VMEM((heads, width, size), jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=interpret, name="delta_rule_forward",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward_call(q, k, v, g, beta, starts, built, do, *, chunk, interpret):
    batch, t, heads = beta.shape
    size, width, n = k.shape[2] // heads, v.shape[2] // heads, t // chunk
    per = _heads_a_step(heads)
    k_like, v_like, beta_like, kept_like = _specs(chunk, size, width, heads, per, lambda m: n - 1 - m)
    return pl.pallas_call(
        functools.partial(_backward_kernel, per=per),
        grid=(batch, n, heads // per),
        in_specs=[k_like, k_like, v_like, k_like, beta_like] + kept_like + [v_like],
        out_specs=[k_like, k_like, v_like, k_like, beta_like],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, width, size), jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=interpret, name="delta_rule_backward",
    )(q, k, v, g, beta, starts, built, do)


def _flat(q, k, v, g, beta):
    """The heads along one dimension of columns, as the kernels' blocks take
    them; ``g`` and ``beta`` float32."""
    batch, t = k.shape[:2]
    f32 = jnp.float32
    return (q.reshape(batch, t, -1), k.reshape(batch, t, -1), v.reshape(batch, t, -1),
            g.astype(f32).reshape(batch, t, -1), beta.astype(f32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_kernels(q, k, v, g, beta, chunk: int, interpret: bool = False):
    """:func:`_chunked` as a forward and a backward kernel (``interpret``: run
    by Pallas' interpreter, anywhere).  Kept between them: the operands and,
    a chunk and head, the state at its start, the scores and the system's
    inverse."""
    out, = _forward_call(*_flat(q, k, v, g, beta), chunk=chunk, interpret=interpret, keep=False)
    return out.reshape(v.shape)


def _rule_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    out, *kept = _forward_call(*_flat(q, k, v, g, beta), chunk=chunk, interpret=interpret, keep=True)
    return out.reshape(v.shape), (q, k, v, g, beta, *kept)


def _rule_kernels_bwd(chunk, interpret, kept, do):
    q, k, v, g, beta, *built = kept
    dq, dk, dv, dg, dbeta = _backward_call(
        *_flat(q, k, v, g, beta), *built, do.reshape(do.shape[0], do.shape[1], -1),
        chunk=chunk, interpret=interpret)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """``o (batch, positions, heads, value size)`` in ``v``'s type, from ``q``,
    ``k (batch, positions, heads, key size)``, ``v`` of ``o``'s shape, ``g`` of
    ``k``'s shape (the log of the decay a channel, at most zero) and ``beta
    (batch, positions, heads)``.  ``q`` and ``k`` come as the layer made them
    (normed, ``q`` scaled); the gate and the norm of a mixer are its caller's.
    The positions divide into chunks of ``chunk`` (a sequence shorter than one
    is one chunk); any other length is refused by name."""
    t = k.shape[1]
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"gated_delta_rule: {t} positions are no whole number of chunks of {chunk}")
    if jax.default_backend() == "tpu" and _kernel_takes(k, v, chunk):
        return _rule_kernels(q, k, v, g, beta, chunk)
    return _chunked(q, k, v, g, beta, chunk)
