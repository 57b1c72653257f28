"""Plain reference for NVIDIA-Nemotron-3-Super-120B-A12B (``nemotron_h``)
causal-LM training: forward pass and loss in straightforward ``jax.numpy``,
float32, written from the published ``config.json`` and the family's
description.  It imports nothing of ``bagua_tpu``.

Every block is ``x <- x + f(norm(x))`` (RMSNorm with a learned scale, one norm
and one addition a block), ``f`` by the block's letter in the pattern, on ``a =
norm(x)``:

* ``M``, the Mamba-2 mixer: ``[z | xBC | dt] = a W_in``; ``xBC <- silu(conv(xBC)
  + b)`` with the convolution as four shifted multiply-adds (``y_t = sum_i w_i
  xBC_{t-3+i}``, zeros before the start); ``xBC`` split into ``x`` (heads of
  ``mamba_head_dim``), ``B`` and ``C`` (groups of ``ssm_state_size``); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; **the recurrence itself**, one
  position after the other, a state ``S`` of head size x state size a head from
  zero: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``, head ``h`` reading the ``B`` and ``C`` of group ``h // (heads /
  groups)``; ``y <- norm_group(y * silu(z)) * w`` over each group's columns (the
  gate first); ``f = y W_out``.  No chunks: the program's chunked scan is held
  to this definition.
* ``*``, attention: ``q``, ``k``, ``v`` projections, no positional embedding,
  each key-value head repeated for its query heads, ``softmax(q k^T /
  sqrt(head_dim) + causal) v``, ``W_o``.
* ``E``, the latent expert layer: ``s = sigmoid(a W_r)``; the
  ``num_experts_per_tok`` experts of largest ``s + b`` (``b`` steers the choice
  alone); ``w = s[chosen] / (sum s[chosen] + 1e-20) x routed_scaling_factor``;
  ``l = a W_lat_in``; ``r = sum_chosen w_i W_down,i relu(W_up,i l)^2``; ``f =
  W_sd relu(W_su a)^2 + r W_lat_out``.  No sort and no buffer: each held expert
  is applied to every token under its weight, zero where it was not chosen.
* ``-``, a dense MLP: ``W_d relu(W_u a)^2``.
* head: ``norm_f(x) W_head``, a matrix of its own; next-token cross entropy,
  mean over the sequence's targets.

Given a *share* it computes what one of the chips that divide a layer
computes: ``experts_held`` of the routed experts (the terms of the others left
out, the weights still normalised over all chosen), ``mamba_heads_held`` of
the mixer's heads in whole groups and ``attention_heads_held`` of the query
heads with the key-value heads they read (the other heads' part of the sum
that ``W_out`` and ``W_o`` take left out); the parameters have the share's
shapes.

Assumed, each listed in ``configs/nemotron-3-super.json``: attention without
positions, the router on the hidden state, the shared expert at the hidden
width beside the latent path, the gate before the group norm, ``dt``
unclamped, 1e-20 in the router's division.

For size only, never for the arithmetic: each block is rebuilt in the backward
pass when the backward pass reaches it (:func:`rebuilt_in_its_turn`), the
recurrence runs in stretches of ``SCAN_STRETCH`` positions that are rebuilt
one at a time (a state a position of the whole sequence would be 4.3 GB a
layer at the benchmark's share), attention takes the queries in blocks, the
held experts and the rows of the head are taken one after the other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 256
HEAD_ROWS = 1024
SCAN_STRETCH = 128
ROUTER_EPS = 1e-20


def rebuilt_in_its_turn(f):
    """``f(x, weights)`` keeping only its arguments for the backward pass,
    which runs ``f`` again, as ``jax.checkpoint(f)`` does, but one call at a
    time: not before the cotangent of its result has arrived, and with
    nothing upstream started before all its gradients are whole
    (``reference/ouro.py`` says what a plain ``jax.checkpoint`` costs on the
    chip).  Values and gradients are ``f``'s own."""

    @jax.custom_vjp
    def g(x, weights):
        return f(x, weights)

    def forward(x, weights):
        return f(x, weights), (x, weights)

    def backward(kept, cotangent):
        x, weights = kept
        x, cotangent = jax.lax.optimization_barrier((x, cotangent))
        return jax.lax.optimization_barrier(jax.vjp(f, x, weights)[1](cotangent))

    g.defvjp(forward, backward)
    return g


def _mixer_widths(sizes):
    """``(heads, groups, inner columns, convolution channels)`` of the share."""
    heads = sizes["mamba_heads_held"][1]
    groups = heads // (sizes["mamba_heads_total"] // sizes["n_groups_total"])
    inner = heads * sizes["mamba_head_dim"]
    return heads, groups, inner, inner + 2 * groups * sizes["ssm_state_size"]


def _key_value_heads_held(sizes):
    group = sizes["attention_heads_total"] // sizes["key_value_heads_total"]
    return max(1, sizes["attention_heads_held"][1] // group)


def layer_shapes(kind: str, sizes):
    h = sizes["hidden_size"]
    if kind == "M":
        heads, _, inner, channels = _mixer_widths(sizes)
        return {"norm": (h,), "w_in": (h, inner + channels + heads),
                "conv_w": (sizes["conv_kernel"], channels), "conv_b": (channels,),
                "dt_bias": (heads,), "a_log": (heads,), "d_skip": (heads,),
                "gate_norm": (inner,), "w_out": (inner, h)}
    if kind == "*":
        size, heads = sizes["head_dim"], sizes["attention_heads_held"][1]
        kv = _key_value_heads_held(sizes)
        return {"norm": (h,), "w_q": (h, heads * size), "w_k": (h, kv * size),
                "w_v": (h, kv * size), "w_o": (heads * size, h)}
    if kind == "E":
        latent, width, held = (sizes["moe_latent_size"], sizes["moe_intermediate_size"],
                               sizes["experts_held"][1])
        shared = sizes["moe_shared_expert_intermediate_size"]
        return {"norm": (h,), "w_router": (h, sizes["routed_experts_total"]),
                "b_router": (sizes["routed_experts_total"],),
                "w_lat_in": (h, latent), "w_lat_out": (latent, h),
                "e_up": (held, latent, width), "e_down": (held, width, latent),
                "s_up": (h, shared), "s_down": (shared, h)}
    if kind == "-":
        return {"norm": (h,), "m_up": (h, sizes["intermediate_size"]),
                "m_down": (sizes["intermediate_size"], h)}
    raise ValueError(f"no block of kind {kind!r}")


def init_params(key, sizes):
    """Seeded float32 parameters, as ``reference/smallthinker_moe.py`` argues
    them: the embedding normal(0, 1), so the stream starts at the size the
    norms put it to; matrices normal(0, 0.02); the ones that write into the
    residual stream (``w_out``, ``w_o``, ``w_lat_out``, the shared expert's and
    the dense MLP's down matrices) normal(0, 0.02 / sqrt(2 x the published
    depth)), so the stream stays a token's own through the depth and every
    router's load near its expectation; norm scales 1 + normal(0, 0.02); the
    router's selection bias normal(0, 0.002); the convolution's taps normal(0,
    1 / sqrt(taps)) and its bias normal(0, 0.02); and the state-space
    parameters as the family initialises them, so that decays span short and
    long memory: ``A_log`` the log of uniform(1, 16), ``dt_bias`` the inverse
    softplus of a log-uniform draw in [``time_step_min``, ``time_step_max``],
    ``D`` 1 + normal(0, 0.02).  No compared gradient is zero or vanishing by
    construction.  ``sizes["init_std"]`` stands in for the matrices' 0.02 where
    a rehearsal's widths are a sixty-fourth of the published (the toy sizes:
    at 0.02 a toy expert's two products and its squared ReLU leave the routed
    part a hundred-thousandth of the shared expert's, and no check sees it)."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    shapes = {"emb": (v, h), "final_norm": (h,), "w_head": (h, v),
              "layers": [layer_shapes(kind, sizes) for kind in sizes["hybrid_override_pattern"]]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    std = sizes.get("init_std", INIT_STD)
    residual_std = std / math.sqrt(2 * sizes["published_layers"])
    low, high = math.log(sizes["time_step_min"]), math.log(sizes["time_step_max"])

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "'emb'" in name:
            return noise
        if "a_log" in name:
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        if "dt_bias" in name:
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, low, high))
            return dt + jnp.log(-jnp.expm1(-dt))
        if "conv_w" in name:
            return noise / math.sqrt(shape[0])
        if "b_router" in name:
            return 0.002 * noise
        if any(n in name for n in ("w_out", "w_o'", "w_lat_out", "s_down", "m_down")):
            return residual_std * noise
        return ("norm" in name or "d_skip" in name) + (
            INIT_STD if noise.ndim < 2 else std) * noise

    return jax.tree.unflatten(
        treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def shifted(x, by: int):
    """``x`` (batch, positions, channels) moved ``by`` positions later, zeros
    moving in."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], axis=1)


def recurrence(x, dt, a, b, c):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``
    from ``S = 0``, one position after the other.  ``x`` (batch, positions,
    heads, size), ``dt`` (batch, positions, heads), ``a`` (heads,), ``b`` and
    ``c`` (batch, positions, heads, state): every head with its group's."""
    batch, t, heads, size = x.shape
    state = b.shape[-1]
    stretch = min(SCAN_STRETCH, t)
    if t % stretch:
        raise ValueError(f"{t} positions do not divide into stretches of {stretch}")

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def one_stretch(s, over):
        return jax.lax.scan(step, s, over)

    def by_stretch(v):  # positions first, in stretches
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // stretch, stretch) + v.shape[1:])

    _, y = jax.lax.scan(one_stretch, jnp.zeros((batch, heads, size, state), jnp.float32),
                        tuple(by_stretch(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def mixer(h, w, sizes):
    batch, t, _ = h.shape
    heads, groups, inner, channels = _mixer_widths(sizes)
    size, state, per = sizes["mamba_head_dim"], sizes["ssm_state_size"], heads // groups
    projected = h @ w["w_in"]
    z, xbc, dt = (projected[..., :inner], projected[..., inner:inner + channels],
                  projected[..., inner + channels:])
    taps = sizes["conv_kernel"]
    xbc = jax.nn.silu(
        sum(w["conv_w"][i] * shifted(xbc, taps - 1 - i) for i in range(taps)) + w["conv_b"])
    x = xbc[..., :inner].reshape(batch, t, heads, size)
    b, c = (jnp.repeat(
        xbc[..., inner + n * groups * state:inner + (n + 1) * groups * state].reshape(
            batch, t, groups, state), per, axis=2) for n in (0, 1))
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["a_log"]), b, c) + w["d_skip"][:, None] * x
    gated = (y.reshape(batch, t, inner) * jax.nn.silu(z)).reshape(batch, t, groups, per * size)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + sizes["layer_norm_epsilon"])
    return (normed.reshape(batch, t, inner) * w["gate_norm"]) @ w["w_out"]


def attention(h, w, sizes):
    b, t, _ = h.shape
    size, heads = sizes["head_dim"], sizes["attention_heads_held"][1]
    kv_heads = _key_value_heads_held(sizes)
    q = (h @ w["w_q"]).reshape(b, t, heads, size)
    k = (h @ w["w_k"]).reshape(b, t, kv_heads, size)
    v = (h @ w["w_v"]).reshape(b, t, kv_heads, size)
    # key-value head n serves query heads n * group .. (n + 1) * group - 1
    k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions do not divide into query blocks of {block}")

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(size)
        i, j = first + jnp.arange(block)[:, None], jnp.arange(t)[None, :]
        s = jnp.where(i >= j, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = q.reshape(b, t // block, block, heads, size).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    return ctx.swapaxes(0, 1).reshape(b, t, heads * size) @ w["w_o"]


def route(h, w, sizes):
    """``(chosen (…, k), weights (…, k))``: the ``k`` experts of largest
    ``sigmoid + b``, their sigmoids over their sum, times the scaling."""
    scores = jax.nn.sigmoid(h @ w["w_router"])
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(w["b_router"]),
                              sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_EPS)
    return chosen, picked * sizes["routed_scaling_factor"]


def experts(h, w, sizes):
    first, held = sizes["experts_held"]
    chosen, picked = route(h, w, sizes)
    lowered = h @ w["w_lat_in"]

    @jax.checkpoint
    def add_expert(total, expert):
        e, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return total + weight * (relu2(lowered @ up) @ down), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(lowered),
                             (first + jnp.arange(held), w["e_up"], w["e_down"]))
    return relu2(h @ w["s_up"]) @ w["s_down"] + routed @ w["w_lat_out"]


def block(x, w, sizes, kind: str):
    h = rms_norm(x, w["norm"], sizes["layer_norm_epsilon"])
    if kind == "M":
        return x + mixer(h, w, sizes)
    if kind == "*":
        return x + attention(h, w, sizes)
    if kind == "E":
        return x + experts(h, w, sizes)
    return x + relu2(h @ w["m_up"]) @ w["m_down"]


def mean_cross_entropy(x, norm, head, targets, eps):
    """Mean over all rows but each sequence's last of the cross entropy of
    ``norm(x) @ head`` against ``targets``, ``HEAD_ROWS`` rows of logits at a
    time."""
    b, t, h = x.shape
    counted = (jnp.arange(t) < t - 1)[None, :] & jnp.ones((b, 1), bool)
    rows = min(HEAD_ROWS, b * t)
    if (b * t) % rows:
        raise ValueError(f"{b * t} rows do not divide into head blocks of {rows}")

    @jax.checkpoint
    def block_sum(args):
        x_blk, target, keep = args
        logp = jax.nn.log_softmax(rms_norm(x_blk, norm, eps) @ head)
        picked = jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    sums = jax.lax.map(block_sum, (
        x.reshape(-1, rows, h), targets.reshape(-1, rows), counted.reshape(-1, rows)))
    return jnp.sum(sums) / (b * (t - 1))


def loss(params, batch, sizes):
    """Next-token cross entropy, mean over each sequence's ``positions - 1``
    targets."""
    ids = batch
    x = params["emb"][ids]
    for w, kind in zip(params["layers"], sizes["hybrid_override_pattern"]):
        x = rebuilt_in_its_turn(lambda x, w, kind=kind: block(x, w, sizes, kind))(x, w)
    return mean_cross_entropy(x, params["final_norm"], params["w_head"],
                              jnp.roll(ids, -1, axis=1), sizes["layer_norm_epsilon"])
