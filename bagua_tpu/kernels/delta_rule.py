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

One implementation, :func:`_chunked`, in plain ``jax.numpy``, on every backend:
the scores and the solve for all chunks at once, the carried state by a
``lax.scan`` over the chunks that does two products a chunk and keeps the
state at each chunk's start, from which the outputs of all chunks are one
batched product afterwards; the backward pass is autodiff's, and what it keeps
is its caller's to decide (``models/solar_open2.py`` puts the layer's whole
core under one ``jax.checkpoint``: the five operands are kept and the chunk's
terms built again).  A Pallas kernel pair with the state in scratch, as
``ssd_scan.py`` has one, would slot in under :func:`gated_delta_rule` by
backend and shape (``PERF.md`` section 6, PR 52, says what the composition
costs on the chip and why the pair is not here yet).
"""

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

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
    return _chunked(q, k, v, g, beta, chunk)
