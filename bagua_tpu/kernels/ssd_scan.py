"""The state-space scan of a Mamba-2 mixer in its chunked (SSD) form.

The recurrence, for head ``h`` of group ``g`` with a state ``S`` of ``head
size x state size`` that starts at zero:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T        y_t = S_t C_{g,t}

``A_h < 0`` is one number a head, ``dt_t > 0`` one a head and position, ``B``
and ``C`` are shared by the heads of a group.  Written out, ``y_t = sum_{s <=
t} exp(sum_{s < r <= t} dt_r A) (C_t . B_s) dt_s x_s``: a causal product whose
"scores" ``C B^T`` carry a decay, and the decay between two positions is the
difference of a running sum of ``dt A``.  :func:`ssd_scan` cuts the positions
into chunks of ``chunk`` and does three matrix products a chunk, all chunks at
once:

* inside a chunk, ``(C B^T * decay, lower triangle) (dt x)``;
* each chunk's own end state, ``(decay to the chunk's end * dt x)^T B``;
* the state carried into a chunk, read through ``C`` and decayed from the
  chunk's start.

Between chunks the carried state is the earlier chunks' end states, each
decayed over the chunks in between: one small product over the chunk axis
(``chunks x chunks`` a head, float32 at the highest precision: the state is
never rounded), no loop.  The decays are differences of a cumulative sum of
``dt A`` *inside the chunk*, in float32, always of a later position minus an
earlier one, so every exponent is at most zero and nothing overflows however
long the sequence or strong the decay.  The operands of the three chunk
products are in ``x``'s type (bf16 in the benchmark) with float32
accumulation; ``dt``, ``A``, the running sums, the decays and the state are
float32.

Plain ``jax.numpy``: a Pallas kernel would keep a chunk's decays and scores in
fast memory where this writes them out (``PERF.md`` section 7).

The backward pass is autodiff of the chunked form, and it keeps what autodiff
keeps.  At the benchmark's share (8,192 positions, 16 heads of 64, one group,
state 128, chunks of 128; 64 chunks) the trace's saved arrays are, a layer:
the decays and their exponents' mask-selected gaps (two of 64 x 16 x 128 x 128
float32, 67.1 MB each, and the mask, 16.8), the rounded decayed scores (33.6),
``dt x`` in float32 and rounded (33.6 + 16.8), the decayed ``dt x`` towards the
chunk's end (33.6 + 16.8), the chunk's own and carried states (33.6 float32 +
16.8 rounded), ``exp`` of the running sum beside the carried read (33.6), the
scores (4.2), ``B`` and ``C`` (2.1 each) and the chunks' decays among
themselves (1.0): 382 MB, of which the compiler, free to fuse and to form the
cheap ones again, holds 160 MB a layer over what the step holds with the whole
scan under ``jax.checkpoint`` (966 to 1,128 MB of temporaries in a plain SGD
step of one mixer layer compiled for the v5e).  Kept, not rebuilt: by that
step's time on the chip 36.04 ms against 36.53 with the scan rebuilt from
``x``, ``dt``, ``B`` and ``C`` (``PERF.md`` section 6, PR 45), and the cell
has the 0.8 GB.
"""

import jax
import jax.numpy as jnp


def _chunked(x, dt, a, b, c, chunk: int):
    f32, dtype = jnp.float32, x.dtype
    batch, t, heads, size = x.shape
    groups, state = b.shape[-2:]
    per = heads // groups
    n = t // chunk
    # (batch, chunks, chunk, groups, heads a group, ...): a head meets its group's B and C
    x = x.reshape(batch, n, chunk, groups, per, size)
    dt = dt.astype(f32).reshape(batch, n, chunk, groups, per)
    b = b.reshape(batch, n, chunk, groups, state)
    c = c.reshape(batch, n, chunk, groups, state)
    run = jnp.cumsum(dt * a.astype(f32).reshape(groups, per), axis=2)  # to and with a position
    dtx = (dt[..., None] * x.astype(f32)).astype(dtype)

    # inside a chunk: position i reads position j <= i under exp(run_i - run_j)
    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    gap = run[:, :, :, None] - run[:, :, None, :]  # (batch, n, i, j, groups, per)
    decay = jnp.exp(jnp.where((i >= j)[:, :, None, None], gap, -jnp.inf))
    scores = jnp.einsum("bnigs,bnjgs->bnijg", c, b, preferred_element_type=f32)
    y = jnp.einsum("bnijgh,bnjghd->bnighd", (scores[..., None] * decay).astype(dtype), dtx,
                   preferred_element_type=f32)

    # a chunk's own end state, and the states carried into the chunks
    to_end = jnp.exp(run[:, :, -1:] - run)
    own = jnp.einsum("bnjghd,bnjgs->bnghds", (to_end[..., None] * dtx.astype(f32)).astype(dtype),
                     b, preferred_element_type=f32)
    # chunk m starts from chunk k's end state, k < m, decayed over the chunks k+1 .. m-1: the
    # exponent is the sum of those chunks' own totals, never a difference of long running sums
    m, k, r = (jnp.arange(n).reshape(shape) for shape in ((n, 1, 1), (1, n, 1), (1, 1, n)))
    over = jnp.einsum("mkr,brgh->bmkgh", ((k < r) & (r < m)).astype(f32), run[:, :, -1],
                      precision=jax.lax.Precision.HIGHEST)
    between = jnp.exp(jnp.where((m > k)[None, :, :, :1, None], over, -jnp.inf))
    carried = jnp.einsum("bmkgh,bkghds->bmghds", between, own,
                         precision=jax.lax.Precision.HIGHEST)
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        "bnghds,bnigs->bnighd", carried.astype(dtype), c, preferred_element_type=f32)
    return y.reshape(batch, t, heads, size).astype(dtype)


def ssd_scan(x, dt, a, b, c, chunk: int = 128):
    """``y (batch, positions, heads, head size)`` in ``x``'s type, from ``x``
    of that shape, ``dt (batch, positions, heads)`` positive, ``a (heads,)``
    negative, and ``b``, ``c (batch, positions, groups, state size)``; head
    ``h`` belongs to group ``h // (heads / groups)``.  The positions divide
    into chunks of ``chunk`` (a sequence shorter than one is one chunk).  The
    ``D x`` term, the gate and the norm of a mixer are its caller's."""
    t, heads, groups = x.shape[1], x.shape[2], b.shape[2]
    chunk = min(chunk, t)
    if t % chunk or heads % groups:
        raise ValueError(
            f"{t} positions in chunks of {chunk}, {heads} heads in {groups} groups: no whole number")
    return _chunked(x, dt, a, b, c, chunk)
