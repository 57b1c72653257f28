"""The state-space scan of a Mamba-2 mixer in its chunked (SSD) form.

The recurrence, for head ``h`` of group ``g`` with a state ``S`` of ``head
size x state size`` that starts at zero:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T        y_t = S_t C_{g,t}

``A_h < 0`` is one number a head, ``dt_t > 0`` one a head and position, ``B``
and ``C`` are shared by the heads of a group.  Written out, ``y_t = sum_{s <=
t} exp(sum_{s < r <= t} dt_r A) (C_t . B_s) dt_s x_s``: a causal product whose
"scores" ``C B^T`` carry a decay, and the decay between two positions is the
difference of a running sum of ``dt A``.  :func:`ssd_scan` cuts the positions
into chunks of ``chunk`` and does three matrix products a chunk:

* inside a chunk, ``(C B^T * decay, lower triangle) (dt x)``;
* each chunk's own end state, ``(decay to the chunk's end * dt x)^T B``;
* the state carried into a chunk, read through ``C`` and decayed from the
  chunk's start.

Between chunks the carried state is the earlier chunks' end states, each
decayed over the chunks in between, in float32 and never rounded.  The decays
are differences of a cumulative sum of ``dt A`` *inside the chunk*, in float32,
always of a later position minus an earlier one, so every exponent is at most
zero and nothing overflows however long the sequence or strong the decay.  The operands of the three chunk
products are in ``x``'s type (bf16 in the benchmark) with float32
accumulation; ``dt``, ``A``, the running sums, the decays and the state are
float32.

Two implementations of that arithmetic, one per backend, chosen by
``jax.default_backend()`` and the shapes and by no option:

* :func:`_chunked`, the three products in plain ``jax.numpy`` over all chunks
  at once, the carried state by one small product over the chunk axis
  (``chunks x chunks`` a head, float32 at the highest precision), the backward
  pass autodiff's.  It runs on every backend but the TPU, and on the TPU for
  shapes the kernels do not take; it is what the kernels are tested against.
  It writes a chunk's decays out: at the benchmark's share (8,192 positions,
  16 heads of 64, one group, state 128, 64 chunks of 128) two arrays of 64 x
  16 x 128 x 128 float32, 67.1 MB each, 382 MB of saved arrays a layer with
  the rest, and was 9.5 ms of a 219 ms step there, 6% of the scan's roofline
  (``PERF.md`` section 6, PR 45 and PR 46).
* on a TPU, a pair of Pallas kernels under one ``jax.custom_vjp``
  (:func:`_scan_kernels`).  A grid step is one chunk of one group, all its
  heads; the chunk axis is innermost and sequential, and the state, ``(state
  size, heads x head size)`` float32, is carried in scratch from chunk to
  chunk, ``S <- exp(total of dt A) S + (decay to the chunk's end * dt x)^T B``:
  the recurrence itself, never rounded between chunks.  The running sum of
  ``dt A``, the decays, the scores ``C B^T`` and their product live in fast
  memory and are never written out.  The forward kernel keeps for the
  backward one the state at each chunk's start (64 x 128 x 1,024 float32,
  33.6 MB a layer at that share) beside the operands, which are alive anyway;
  the backward kernel walks the chunks from the last to the first with the
  state's cotangent in scratch, builds the chunk's decays and scores again,
  and writes ``dx``, ``dB`` and ``dC`` (summed over the group's heads), the
  cotangent of ``dt`` where it multiplies ``x``, and that of ``dt A``, from
  which ``dt``'s other part and ``dA`` are one pass over ``(positions,
  heads)`` outside.  Products take operands in ``x``'s type and accumulate in
  float32 as :func:`_chunked`'s; sums of float32 columns go through the matrix
  unit at the highest precision.  The kernels take positions in whole chunks,
  a chunk and a state that are multiples of 128, and a head size that divides
  128 or is a multiple of it, with a group's heads x head size a multiple of
  128.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b

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


# -- the same arithmetic as a pair of Pallas kernels -------------------------------
#
# A grid step is one chunk of one group of one sequence, all the group's heads; the chunk
# axis is innermost and sequential, and the state crosses it in scratch.  The state is held
# transposed, ``(state size, heads a group x head size)``: a head is then a run of lanes, as
# in ``x``, the decay over a chunk multiplies it as a row, and reading it through ``C`` is a
# plain product.  The lanes are worked a *tile* at a time: 128 lanes, or a head where a head
# is wider; a head inside a tile of several is picked by zeroing the other heads' lanes of one
# operand, which costs the matrix unit nothing (it is 128 wide either way).


def _kernel_takes(x, b, chunk: int) -> bool:
    """Whether the shapes are ones the kernels take: every block's last two
    dimensions whole tiles."""
    size = x.shape[3]
    groups, state = b.shape[2:]
    width = x.shape[2] // groups * size
    return (chunk % _LANES == 0 and state % _LANES == 0 and width % _LANES == 0
            and (size % _LANES == 0 or _LANES % size == 0))


def _tiles(width: int, size: int):
    """``(lanes, heads)`` of each tile of a group's ``width`` lanes."""
    lanes = max(size, _LANES)
    return [(slice(at, at + lanes), list(range(at // size, (at + lanes) // size)))
            for at in range(0, width, lanes)]


def _running(v, axis: int, backwards: bool = False):
    """The running sum of ``v`` along ``axis``, to and with a position (from
    and with it, ``backwards``), in log2 steps of shift and add.  The order of
    the additions depends on the position alone, so a column of one array and
    the same numbers as a row of another sum to the same bits."""
    n = v.shape[axis]
    at = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    step = 1
    while step < n:
        if backwards:
            v = v + jnp.where(at < n - step, pltpu.roll(v, n - step, axis), 0.0)
        else:
            v = v + jnp.where(at >= step, pltpu.roll(v, step, axis), 0.0)
        step *= 2
    return v


def _spread(v, heads, size: int, lanes: int):
    """``(chunk, lanes)`` from ``v (chunk, heads a group)``: each head of the
    tile's column over that head's lanes."""
    out = jnp.broadcast_to(v[:, heads[0]:heads[0] + 1], (v.shape[0], lanes))
    if len(heads) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for k, h in enumerate(heads[1:], 1):
            out = jnp.where(lane >= k * size, v[:, h:h + 1], out)
    return out


def _only(v, k: int, heads, size: int):
    """``v (chunk, lanes)`` with the lanes of every head of the tile but the
    ``k``-th zeroed."""
    if len(heads) == 1:
        return v
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where((lane >= k * size) & (lane < (k + 1) * size), v, jnp.zeros_like(v))


def _chunk_terms(dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref):
    """What both kernels build of a chunk before they turn to the heads."""
    f32 = jnp.float32
    dt = dt_ref[...]  # (chunk, heads a group)
    run = _running(dt * a_ref[...], 0)
    run_t = _running(dtt_ref[...] * at_ref[...], 1)  # (heads a group, chunk): the same bits
    chunk = dt.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    b, c = b_ref[...], c_ref[...]
    scores = jax.lax.dot_general(c, b, _NT, preferred_element_type=f32)

    def decay(h):  # position i reads position j <= i under exp(run_i - run_j)
        return jnp.exp(jnp.where(i >= j, run[:, h:h + 1] - run_t[h:h + 1, :], -jnp.inf))

    return dt, jnp.exp(run), jnp.exp(run[chunk - 1:chunk] - run), b, c, scores, decay


def _forward_kernel(x_ref, dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, y_ref, *rest, size):
    state = rest[-1]  # (state size, width) float32, at the chunk's start
    f32, dtype = jnp.float32, x_ref.dtype
    chunk, width = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if len(rest) == 2:  # kept for the backward pass
        rest[0][...] = state[...]
    dt, from_start, to_end, b, c, scores, decay = _chunk_terms(
        dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref)
    b_t = b.T  # once a chunk, for every tile's state
    for at, heads in _tiles(width, size):
        lanes = at.stop - at.start
        dtx = (_spread(dt, heads, size, lanes) * x_ref[:, at].astype(f32)).astype(dtype)
        over = _spread(from_start, heads, size, lanes)
        start = state[:, at]
        y = over * jnp.dot(c, start.astype(dtype), preferred_element_type=f32)
        for k, h in enumerate(heads):
            y = y + jnp.dot((scores * decay(h)).astype(dtype), _only(dtx, k, heads, size),
                            preferred_element_type=f32)
        y_ref[:, at] = y.astype(dtype)
        ending = (_spread(to_end, heads, size, lanes) * dtx.astype(f32)).astype(dtype)
        state[:, at] = over[chunk - 1:chunk] * start + jnp.dot(
            b_t, ending, preferred_element_type=f32)


def _backward_kernel(x_ref, dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref, starts_ref, dy_ref,
                     dx_ref, ddt_ref, dda_ref, ddat_ref, db_ref, dc_ref, dstate, *, size):
    """One chunk, the chunks taken from the last to the first; ``dstate`` is
    the cotangent of the state at the chunk's end.

    The cotangent of the running sum ``run`` of ``dt a`` is a sum of flows:
    whatever position ``i`` reads of position ``j`` under ``exp(run_i -
    run_j)`` gives its product with its cotangent to ``run_i`` and takes it
    from ``run_j``.  Every flow is formed once and both ends take the same
    number, so a sum of them over positions cancels to float32 rounding as it
    does on paper: inside the chunk the rows' sums (down, into ``dda``) and the
    columns' (across, into ``ddat``) of one array; between chunks what a
    position sends to the chunk's end leaves that position and arrives, summed,
    at the chunk's last."""
    f32, dtype = jnp.float32, x_ref.dtype
    chunk, width = x_ref.shape
    per = dt_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    dt, from_start, to_end, b, c, scores, decay = _chunk_terms(
        dt_ref, dtt_ref, a_ref, at_ref, b_ref, c_ref)
    c_t = c.T  # once a chunk, for every tile's state cotangent
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    down = jax.lax.broadcasted_iota(jnp.int32, (chunk, per), 1)
    across = jax.lax.broadcasted_iota(jnp.int32, (per, chunk), 0)
    d_scores = jnp.zeros((chunk, chunk), f32)
    d_b = jnp.zeros(b.shape, f32)
    d_c = jnp.zeros(c.shape, f32)
    d_run = jnp.zeros((chunk, per), f32)  # of the running sum of dt a, positions down
    d_run_t = jnp.zeros((per, chunk), f32)  # and positions across
    d_dt = jnp.zeros((chunk, per), f32)  # of dt where it multiplies x
    for at, heads in _tiles(width, size):
        lanes = at.stop - at.start
        x = x_ref[:, at].astype(f32)
        dy = dy_ref[:, at]
        dy_f32 = dy.astype(f32)
        step = _spread(dt, heads, size, lanes)
        over = _spread(from_start, heads, size, lanes)
        left = _spread(to_end, heads, size, lanes)
        dtx = (step * x).astype(dtype)
        ending = (left * dtx.astype(f32)).astype(dtype)
        start, d_end = starts_ref[:, at], dstate[:, at]
        start_r, d_end_r = start.astype(dtype), d_end.astype(dtype)  # rounded, as read forward
        read = (over * dy_f32).astype(dtype)  # dy where it reads the carried state
        sent = jnp.dot(b, d_end_r, preferred_element_type=f32)
        d_dtx = left * sent
        for k, h in enumerate(heads):
            decayed = decay(h)
            weighted = scores * decayed
            dy_h = _only(dy, k, heads, size)
            d_weighted = jax.lax.dot_general(dy_h, dtx, _NT, preferred_element_type=f32)
            d_scores = d_scores + decayed * d_weighted
            flow = weighted * d_weighted
            d_run = d_run + jnp.where(down == h, jnp.sum(flow, axis=1, keepdims=True), 0.0)
            d_run_t = d_run_t - jnp.where(across == h, jnp.sum(flow, axis=0, keepdims=True), 0.0)
            d_dtx = d_dtx + jax.lax.dot_general(weighted.astype(dtype), dy_h, _TN,
                                                preferred_element_type=f32)
        dx_ref[:, at] = (step * d_dtx).astype(dtype)
        # the carried state, read at a position, flows from the chunk's start to it; dt x sent
        # to the chunk's end flows from its position to the last, where the carried state's
        # own passage arrives too
        sending = ending.astype(f32) * sent
        flows = (dy_f32 * over * jnp.dot(c, start_r, preferred_element_type=f32)
                 - sending + jnp.where(last, jnp.sum(
                     sending + over[chunk - 1:chunk] * start * d_end, axis=0, keepdims=True), 0.0))
        # each head's lanes summed into that head's column
        column = (jax.lax.broadcasted_iota(jnp.int32, (lanes, per), 1)
                  == heads[0] + jax.lax.broadcasted_iota(jnp.int32, (lanes, per), 0) // size
                  ).astype(f32)
        d_run = d_run + jnp.dot(flows, column, precision=_HIGHEST, preferred_element_type=f32)
        d_dt = d_dt + jnp.dot(x * d_dtx, column, precision=_HIGHEST, preferred_element_type=f32)
        d_c = d_c + jax.lax.dot_general(read, start_r, _NT, preferred_element_type=f32)
        d_b = d_b + jax.lax.dot_general(ending, d_end_r, _NT, preferred_element_type=f32)
        dstate[:, at] = over[chunk - 1:chunk] * d_end + jnp.dot(
            c_t, read, preferred_element_type=f32)
    d_scores = d_scores.astype(dtype)
    dc_ref[...] = (d_c + jnp.dot(d_scores, b, preferred_element_type=f32)).astype(dc_ref.dtype)
    db_ref[...] = (d_b + jax.lax.dot_general(d_scores, c, _TN, preferred_element_type=f32)
                   ).astype(db_ref.dtype)
    ddt_ref[...] = d_dt
    # run is dt a summed to and with a position: dt a at a position takes every later run's
    dda_ref[...] = _running(d_run, 0, backwards=True)
    ddat_ref[...] = _running(d_run_t, 1, backwards=True)


def _by_group(dt, a, groups: int):
    """``dt`` and ``a`` a group at a time, positions down and positions across."""
    batch, t, heads = dt.shape
    dt = dt.astype(jnp.float32).reshape(batch, t, groups, heads // groups)
    a = a.astype(jnp.float32).reshape(groups, 1, heads // groups)
    return dt.transpose(0, 2, 1, 3), dt.transpose(0, 2, 3, 1), a, a.transpose(0, 2, 1)


def _specs(chunk: int, width: int, per: int, state: int, at):
    """Block specifications of ``x``-like, the four of ``dt`` and ``a``, and
    ``b``-like operands; ``at`` maps the grid's chunk index to the chunk."""
    x = pl.BlockSpec((None, chunk, width), lambda i, g, n: (i, at(n), g))
    dt = pl.BlockSpec((None, None, chunk, per), lambda i, g, n: (i, g, at(n), 0))
    dtt = pl.BlockSpec((None, None, per, chunk), lambda i, g, n: (i, g, 0, at(n)))
    a = pl.BlockSpec((None, 1, per), lambda i, g, n: (g, 0, 0))
    a_t = pl.BlockSpec((None, per, 1), lambda i, g, n: (g, 0, 0))
    b = pl.BlockSpec((None, chunk, state), lambda i, g, n: (i, at(n), g))
    starts = pl.BlockSpec((None, None, None, state, width), lambda i, g, n: (i, g, at(n), 0, 0))
    return x, dt, dtt, a, a_t, b, starts


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# The two calls are jitted, on the kernels' own flat operands, so that a model's mixers of one
# shape share one trace and one lowering of each kernel: the bodies are written out head by
# head, 2.2 s of a start for five mixers where one takes 0.5.  Nothing else is inside the jit:
# with the reshapes to and from the caller's shapes inside it the compiled step kept three
# copies of bf16[1,8192,1024] a mixer that it otherwise folds away (PERF.md section 6, PR 46).


@functools.partial(jax.jit, static_argnames=("size", "chunk", "interpret", "keep"))
def _forward_call(x, dt_g, dt_t, a_g, a_t, b, c, *, size, chunk, interpret, keep):
    batch, t = x.shape[:2]
    groups, per = dt_g.shape[1], dt_g.shape[3]
    state, width, n = b.shape[2] // groups, per * size, t // chunk
    x_like, dt_spec, dtt_spec, a_spec, at_spec, b_like, starts = _specs(
        chunk, width, per, state, lambda m: m)
    flat = jax.ShapeDtypeStruct(x.shape, x.dtype)
    kept = jax.ShapeDtypeStruct((batch, groups, n, state, width), jnp.float32)
    return pl.pallas_call(
        functools.partial(_forward_kernel, size=size),
        grid=(batch, groups, n),
        in_specs=[x_like, dt_spec, dtt_spec, a_spec, at_spec, b_like, b_like],
        out_specs=[x_like, starts] if keep else [x_like],
        out_shape=[flat, kept] if keep else [flat],
        scratch_shapes=[pltpu.VMEM((state, width), jnp.float32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret, name="ssd_scan_forward",
    )(x, dt_g, dt_t, a_g, a_t, b, c)


@functools.partial(jax.jit, static_argnames=("size", "chunk", "interpret"))
def _backward_call(x, dt_g, dt_t, a_g, a_t, b, c, starts, dy, *, size, chunk, interpret):
    batch, t = x.shape[:2]
    groups, per = dt_g.shape[1], dt_g.shape[3]
    state, width, n = b.shape[2] // groups, per * size, t // chunk
    x_like, dt_spec, dtt_spec, a_spec, at_spec, b_like, starts_spec = _specs(
        chunk, width, per, state, lambda m: n - 1 - m)
    by_group = jax.ShapeDtypeStruct(dt_g.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, size=size),
        grid=(batch, groups, n),
        in_specs=[x_like, dt_spec, dtt_spec, a_spec, at_spec, b_like, b_like, starts_spec,
                  x_like],
        out_specs=[x_like, dt_spec, dt_spec, dtt_spec, b_like, b_like],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), by_group, by_group,
                   jax.ShapeDtypeStruct(dt_t.shape, jnp.float32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype), jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((state, width), jnp.float32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret, name="ssd_scan_backward",
    )(x, dt_g, dt_t, a_g, a_t, b, c, starts, dy)


def _flat(x, b, c):
    """``x`` with its heads and ``b``, ``c`` with their groups along one
    dimension of columns, as the kernels' blocks take them."""
    batch, t = x.shape[:2]
    return x.reshape(batch, t, -1), b.reshape(batch, t, -1), c.reshape(batch, t, -1)


def _scan_forward(x, dt, a, b, c, chunk: int, interpret: bool, keep: bool):
    """``y`` and, ``keep``, the state at each chunk's start ``(batch, groups,
    chunks, state size, heads a group x head size)`` float32."""
    x_flat, b_flat, c_flat = _flat(x, b, c)
    out = _forward_call(x_flat, *_by_group(dt, a, b.shape[2]), b_flat, c_flat,
                        size=x.shape[3], chunk=chunk, interpret=interpret, keep=keep)
    return (out[0].reshape(x.shape), *out[1:])


def _scan_backward(x, dt, a, b, c, starts, dy, chunk: int, interpret: bool):
    dt_g, dt_t, a_g, a_t = _by_group(dt, a, b.shape[2])
    x_flat, b_flat, c_flat = _flat(x, b, c)
    dx, ddt, dda, dda_t, db, dc = _backward_call(
        x_flat, dt_g, dt_t, a_g, a_t, b_flat, c_flat, starts, dy.reshape(x_flat.shape),
        size=x.shape[3], chunk=chunk, interpret=interpret)
    # dt a is where the decays come from: dt's part of it beside dt's part in dt x, and a's
    dda = dda + dda_t.transpose(0, 1, 3, 2)
    ddt = (ddt + dda * a_g[None]).transpose(0, 2, 1, 3).reshape(dt.shape)
    da = jnp.sum(dda * dt_g, axis=(0, 2)).reshape(a.shape)
    return (dx.reshape(x.shape), ddt.astype(dt.dtype), da.astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """:func:`_chunked` as a forward and a backward kernel (``interpret``: run
    by Pallas' interpreter, anywhere).  Kept between them: the operands and
    the state at each chunk's start."""
    return _scan_forward(x, dt, a, b, c, chunk, interpret, keep=False)[0]


def _scan_kernels_fwd(x, dt, a, b, c, chunk, interpret):
    y, starts = _scan_forward(x, dt, a, b, c, chunk, interpret, keep=True)
    return y, (x, dt, a, b, c, starts)


def _scan_kernels_bwd(chunk, interpret, kept, dy):
    return _scan_backward(*kept, dy, chunk, interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


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
    if jax.default_backend() == "tpu" and _kernel_takes(x, b, chunk):
        return _scan_kernels(x, dt, a, b, c, chunk)
    return _chunked(x, dt, a, b, c, chunk)
