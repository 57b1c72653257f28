"""Plain reference for Solar-Open2-250B (``solar_open2``, upstage) causal-LM
training: forward pass and loss in straightforward ``jax.numpy``, float32,
written from the published ``config.json`` and, for what no key states, the
Kimi Linear report's KDA layer (arXiv:2510.26692) and the family's convention
(each listed under ``assumed`` in ``configs/solar-open2-250b.json``).  It
imports nothing of ``bagua_tpu``.

Every layer (``x`` the residual stream; RMSNorm with a learned scale, ``eps``
``rms_norm_eps``; no positions anywhere): ``h = norm_in(x)``, ``x += mixer(h)``,
``u = norm_post(x)``, ``x += experts(u)``.

1. The GQA mixer (layers in ``gqa_layers``): ``q = h W_q`` onto ``H`` heads of
   ``head_dim``, ``k``, ``v`` onto ``H / 8``, key-value head ``j`` serving query
   heads ``8 j .. 8 j + 7``; ``ctx = softmax(q k^T / sqrt(head_dim) + causal)
   v``; the gate ``a = sigmoid(h W_g)``, one value a head *column* (``W_g`` of
   ``hidden x H head_dim``), from the same normed input; ``mixer = (ctx * a)
   W_o``.
2. The KDA mixer (every other layer), ``H`` heads of ``d`` keys and values,
   ``conv`` a depthwise causal convolution of four taps a column (``y_t = sum_i
   w_i x_{t-3+i}``, zeros before the start, no bias): ``q~ = silu(conv(h
   W_q))``, ``k~`` and ``v`` alike; a head's ``q = q~ / sqrt(||q~||^2 + 1e-6) /
   sqrt(d)``, ``k = k~ / sqrt(||k~||^2 + 1e-6)``; ``g = -exp(A_log[head])
   softplus((h W_f1) W_f2 + dt_bias)``, one a *channel*; ``beta = 2 sigmoid(h
   W_b)``, one a head; **the recurrence itself**, one position after the other,
   a state ``S`` of ``d x d`` a head from zero: ``S' = exp(g_t)[:, None] *
   S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``;
   ``y = norm_d(o_t) * w * sigmoid((h W_g1) W_g2 + b_g)``, the norm over a
   head's ``d`` columns with one scale ``w`` of ``d`` for all heads; ``mixer = y
   W_o``.  No chunks, no triangular solve: the program's chunked rule is held
   to this definition.
3. The experts: ``s = sigmoid(u W_r)``; the ``k`` experts of largest ``s + b``
   (``b`` steers the choice alone); ``w = s[chosen] / (sum s[chosen] + 1e-20) *
   routed_scaling_factor``; ``experts = E_shared(u) + sum_chosen w_i E_i(u)``,
   SwiGLU units.  No sort and no buffer: each held expert is applied to every
   token under its weight, zero where it was not chosen.
4. Head: ``norm(x) W_head``, a matrix of its own; next-token cross entropy,
   mean over the sequence's targets.

Given a *share* it computes what one of the chips that divide a layer
computes: ``experts_held`` of the routed experts (the terms of the others left
out, the weights still normalised over all chosen) and ``heads_held`` of each
mixer's heads (the other heads' part of the sum that ``W_o`` takes left out);
the parameters have the share's shapes.

For size only, never for the arithmetic: each layer is rebuilt in the backward
pass when the backward pass reaches it (:func:`rebuilt_in_its_turn`), the
recurrence runs in stretches of ``SCAN_STRETCH`` positions that are rebuilt one
at a time (a state a position of the whole sequence would be 4.3 GB a layer at
the benchmark's share), attention takes the queries in blocks, the held experts
and the rows of the head are taken one after the other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.002
QUERY_BLOCK = 256
HEAD_ROWS = 1024
SCAN_STRETCH = 128
ROUTER_EPS = 1e-20
L2_EPS = 1e-6
#: the range of the KDA layer's initial time steps, and of ``exp(A_log)``
DT_RANGE = (0.001, 0.1)
A_RANGE = (1.0, 16.0)


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


def _key_value_heads_held(sizes):
    group = sizes["attention_heads_total"] // sizes["key_value_heads_total"]
    return max(1, sizes["heads_held"][1] // group)


def layer_shapes(n: int, sizes):
    h, heads = sizes["hidden_size"], sizes["heads_held"][1]
    width, held, total = (sizes["moe_intermediate_size"], sizes["experts_held"][1],
                          sizes["routed_experts_total"])
    if n in sizes["gqa_layers"]:
        size, kv = sizes["head_dim"], _key_value_heads_held(sizes)
        mixer = {"w_q": (h, heads * size), "w_k": (h, kv * size), "w_v": (h, kv * size),
                 "w_g": (h, heads * size), "w_o": (heads * size, h)}
    else:
        size, taps = sizes["kda_head_dim"], sizes["short_conv_kernel_size"]
        inner = heads * size
        mixer = {"w_q": (h, inner), "w_k": (h, inner), "w_v": (h, inner),
                 "conv_q": (taps, inner), "conv_k": (taps, inner), "conv_v": (taps, inner),
                 "w_f1": (h, size), "w_f2": (size, inner), "dt_bias": (inner,), "a_log": (heads,),
                 "w_b": (h, heads), "w_g1": (h, size), "w_g2": (size, inner), "b_g": (inner,),
                 "o_norm": (size,), "w_o": (inner, h)}
    return {"norm_in": (h,), **mixer, "norm_post": (h,),
            "w_router": (h, total), "b_router": (total,),
            "e_gate": (held, h, width), "e_up": (held, h, width), "e_down": (held, width, h),
            "s_gate": (h, width), "s_up": (h, width), "s_down": (width, h)}


def init_params(key, sizes):
    """Seeded float32 parameters, as ``reference/nemotron_h.py`` argues them:
    the embedding normal(0, 1), so the stream starts at the size the norms put
    it to; matrices normal(0, 0.02); the ones that write into the residual
    stream (``w_o``, ``s_down``, ``e_down``) normal(0, 0.02 / sqrt(2 x the
    published depth)); norm scales 1 + normal(0, 0.02); the gate's bias
    normal(0, 0.02); the router's selection bias normal(0, 0.002); the
    convolutions' taps normal(0, 1 / sqrt(taps)); and the decay's parameters as
    the Kimi Linear KDA layer initialises them, so that decays are neither all
    one nor all zero: ``A_log`` the log of uniform(1, 16) a head, ``dt_bias``
    the inverse softplus of a log-uniform draw in [0.001, 0.1] a channel.  No
    compared gradient is zero or vanishing by construction.
    ``sizes["init_std"]`` stands in for the matrices' 0.02, and
    ``sizes["dt_range"]`` for the time steps' range, where the toy sizes set
    them."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    shapes = {"emb": (v, h), "final_norm": (h,), "w_head": (h, v),
              "layers": [layer_shapes(n, sizes) for n in range(sizes["num_hidden_layers"])]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    std = sizes.get("init_std", INIT_STD)
    residual_std = std / math.sqrt(2 * sizes["published_layers"])

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "'emb'" in name:
            return noise
        if "a_log" in name:
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, *A_RANGE))
        if "dt_bias" in name:
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, *map(math.log, sizes.get("dt_range", DT_RANGE))))
            return dt + jnp.log(-jnp.expm1(-dt))
        if "conv_" in name:
            return noise / math.sqrt(shape[0])
        if "b_router" in name:
            return BIAS_STD * noise
        if "w_o'" in name or "_down" in name:
            return residual_std * noise
        return ("norm" in name) + (INIT_STD if noise.ndim < 2 else std) * noise

    return jax.tree.unflatten(
        treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def shifted(x, by: int):
    """``x`` (batch, positions, channels) moved ``by`` positions later, zeros
    moving in."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], axis=1)


def conv_silu(x, taps):
    """``silu(sum_i taps[i] x_{t - (L - 1) + i})``: the last tap on the current
    position."""
    last = taps.shape[0] - 1
    return jax.nn.silu(sum(taps[i] * shifted(x, last - i) for i in range(last + 1)))


def attention(h, w, sizes):
    b, t, _ = h.shape
    size, heads = sizes["head_dim"], sizes["heads_held"][1]
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
    ctx = ctx.swapaxes(0, 1).reshape(b, t, heads * size)
    if sizes["use_gqa_gate"]:
        ctx = ctx * jax.nn.sigmoid(h @ w["w_g"])
    return ctx @ w["w_o"]


def delta_rule(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` with ``S' = exp(g_t)[:, None] S_{t-1}``, ``S_t = S' +
    beta_t k_t (v_t - S'^T k_t)^T`` from ``S = 0``, one position after the
    other.  ``q``, ``k``, ``v``, ``g`` (batch, positions, heads, size), ``beta``
    (batch, positions, heads)."""
    batch, t, heads, size = k.shape
    stretch = min(SCAN_STRETCH, t)
    if t % stretch:
        raise ValueError(f"{t} positions do not divide into stretches of {stretch}")

    def step(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = jnp.exp(g_t)[..., None] * s
        held = jnp.sum(s * k_t[..., None], axis=-2)  # S'^T k
        s = s + (beta_t[..., None] * k_t)[..., None] * (v_t - held)[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    @jax.checkpoint
    def one_stretch(s, over):
        return jax.lax.scan(step, s, over)

    def by_stretch(x):  # positions first, in stretches
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // stretch, stretch) + x.shape[1:])

    _, o = jax.lax.scan(one_stretch, jnp.zeros((batch, heads, size, v.shape[-1]), jnp.float32),
                        tuple(by_stretch(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def kda(h, w, sizes):
    b, t, _ = h.shape
    heads, size = sizes["heads_held"][1], sizes["kda_head_dim"]
    by_head = (b, t, heads, size)
    q, k, v = (conv_silu(h @ w["w_" + n], w["conv_" + n]).reshape(by_head) for n in "qkv")
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(size)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        ((h @ w["w_f1"]) @ w["w_f2"] + w["dt_bias"]).reshape(by_head))
    beta = jax.nn.sigmoid(h @ w["w_b"]) * (2.0 if sizes["kda_allow_neg_eigval"] else 1.0)
    o = delta_rule(q, k, v, g, beta)
    z = ((h @ w["w_g1"]) @ w["w_g2"] + w["b_g"]).reshape(by_head)
    y = rms_norm(o, w["o_norm"], sizes["rms_norm_eps"]) * jax.nn.sigmoid(z)
    return y.reshape(b, t, heads * size) @ w["w_o"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(u, w, sizes):
    """``(chosen (…, k), weights (…, k))``: the ``k`` largest of ``s + b``,
    and ``s`` of those over their sum, times the scaling factor."""
    scores = jax.nn.sigmoid(u @ w["w_router"])
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(w["b_router"]), sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_EPS)
    return chosen, picked * sizes["routed_scaling_factor"]


def experts(u, w, sizes):
    first, held = sizes["experts_held"]
    chosen, picked = route(u, w, sizes)

    @jax.checkpoint
    def one_expert(expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return weight * swiglu(u, gate, up, down)

    routed, _ = jax.lax.scan(
        lambda total, expert: (total + one_expert(expert), None), jnp.zeros_like(u),
        (first + jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return routed + swiglu(u, w["s_gate"], w["s_up"], w["s_down"])


def layer(x, w, sizes, n: int):
    eps = sizes["rms_norm_eps"]
    h = rms_norm(x, w["norm_in"], eps)
    x = x + (attention(h, w, sizes) if n in sizes["gqa_layers"] else kda(h, w, sizes))
    return x + experts(rms_norm(x, w["norm_post"], eps), w, sizes)


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
    for n, w in enumerate(params["layers"]):
        x = rebuilt_in_its_turn(lambda x, w, n=n: layer(x, w, sizes, n))(x, w)
    return mean_cross_entropy(x, params["final_norm"], params["w_head"],
                              jnp.roll(ids, -1, axis=1), sizes["rms_norm_eps"])
