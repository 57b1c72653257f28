"""Plain reference for LFM2-8B-A1B (``lfm2_moe``) causal-LM training: forward
pass and loss in straightforward ``jax.numpy``, float32, written from the
published ``config.json`` and the family's description.  It imports nothing
of ``bagua_tpu``.

The layers (``x`` the residual stream; RMSNorm with a learned scale; no bias):
``x += mixer(norm(x))``, ``x += ffn(norm(x))``.

* ``conv`` mixer: ``[B | C | u] = h W_in``; ``z = B * u``; ``c_t = sum_j
  taps[j] * z_{t-j}`` per channel over ``conv_L_cache`` taps, ``z`` before
  position 0 zero; ``y = (C * c) W_out``.
* ``full_attention`` mixer: ``q = h W_q`` in ``num_attention_heads`` heads,
  ``k = h W_k`` and ``v = h W_v`` in ``num_key_value_heads``; RMSNorm over the
  columns of every head of ``q`` and of ``k`` (one scale each); rotary
  embedding on all columns, column ``i`` paired with ``i + size / 2``; each
  key-value head repeated for its group of query heads; ``softmax(q k^T /
  sqrt(size) + causal) v``; ``W_o``.
* dense layer (the first ``num_dense_layers``): ``W_2(silu(W_1 h) * W_3 h)``.
* expert layer: ``s = sigmoid(h W_r)``; the ``k`` experts of largest ``s + b``;
  ``w = s[chosen] / (sum s[chosen] + eps) * routed_scaling_factor``;
  ``sum_chosen w_i E_i(h)``, no shared expert.  Given a *share*
  (``experts_held``) it adds the terms of the chosen experts in that range
  only, the weights still normalised over all ``k``: what one of the chips
  that divide the layer's experts computes.  No sort: each held expert is
  applied to every token under its weight, zero where it was not chosen.
* head: ``norm(x) Emb^T``, the embedding's own matrix; next-token cross
  entropy, mean over the sequence's targets.

Assumed, each listed in ``configs/lfm2-8b-a1b.json``: the tied output matrix,
``eps`` 1e-6, ``b`` seeded and fixed.

For size only, never for the arithmetic: each layer is rematerialised in the
backward pass, attention takes the queries in blocks (each against all keys
under the causal mask), the held experts and the rows of the head are taken
one after the other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
#: of the router's selection bias ``b``: a hundredth of the spread of a score
#: from token to token (0.2), so that it steers the choice at the margin as
#: the method has it and does not unbalance the experts' load, which is what
#: a deployment's ``b`` is there to prevent (at 0.02 an expert's share of the
#: tokens moves by a sixth and this chip's rows by 6% from seed to seed)
BIAS_STD = 0.002
QUERY_BLOCK = 512
HEAD_ROWS = 1024


def init_params(key, sizes):
    """Seeded float32 parameters: matrices normal(0, 0.02), norm scales
    around one, the convolution's taps normal(0, 1 / sqrt(L)) so that the
    mixer's output is near its input's size: no compared gradient is zero or
    vanishing by construction (the router's selection bias ``b``, normal(0,
    0.002), takes none by definition)."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    size, taps = h // heads, sizes["conv_L_cache"]
    width, held = sizes["moe_intermediate_size"], sizes["experts_held"][1]

    def layer(n):
        shapes = {"operator_norm": (h,), "ffn_norm": (h,)}
        if sizes["layer_types"][n] == "conv":
            shapes.update(w_in=(h, 3 * h), taps=(taps, h), w_out=(h, h))
        else:
            shapes.update(w_q=(h, heads * size), w_k=(h, kv_heads * size),
                          w_v=(h, kv_heads * size), q_norm=(size,), k_norm=(size,),
                          w_o=(heads * size, h))
        if n < sizes["num_dense_layers"]:
            i = sizes["intermediate_size"]
            shapes.update(w_1=(h, i), w_3=(h, i), w_2=(i, h))
        else:
            total = sizes["routed_experts_total"]
            shapes.update(w_router=(h, total), b_router=(total,), e_gate=(held, h, width),
                          e_up=(held, h, width), e_down=(held, width, h))
        return shapes

    shapes = {"emb": (v, h), "final_norm": (h,),
              "layers": [layer(n) for n in range(sizes["num_hidden_layers"])]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "taps" in name:
            return noise / math.sqrt(taps)
        if "b_router" in name:
            return BIAS_STD * noise
        return ("norm" in name) + INIT_STD * noise

    return jax.tree.unflatten(treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def short_conv(h, w):
    """The gated short convolution: a direct sum over the taps."""
    t = h.shape[1]
    gate_b, gate_c, u = jnp.split(h @ w["w_in"], 3, axis=-1)
    z = gate_b * u
    c = jnp.zeros_like(z)
    for j in range(w["taps"].shape[0]):
        # z_{t-j}: j zero positions before position 0
        earlier = jnp.concatenate([jnp.zeros_like(z[:, :j]), z[:, :t - j]], axis=1)
        c = c + w["taps"][j] * earlier
    return (gate_c * c) @ w["w_out"]


def rotary(x, theta):
    """``x`` (batch, positions, heads, size): column ``i`` and column ``i +
    size / 2`` turned by ``position * theta ** (-2i / size)``."""
    size = x.shape[-1]
    freq = theta ** (-jnp.arange(0, size, 2, dtype=jnp.float32) / size)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :size // 2], x[..., size // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def attention(h, w, sizes):
    b, t, hidden = h.shape
    heads, kv_heads = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    size, eps, theta = hidden // heads, sizes["norm_eps"], sizes["rope_theta"]
    q = rotary(rms_norm((h @ w["w_q"]).reshape(b, t, heads, size), w["q_norm"], eps), theta)
    k = rotary(rms_norm((h @ w["w_k"]).reshape(b, t, kv_heads, size), w["k_norm"], eps), theta)
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
        rows = first + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = q.reshape(b, t // block, block, heads, size).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    return ctx.swapaxes(0, 1).reshape(b, t, heads * size) @ w["w_o"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def expert_mlp(h, w, sizes):
    first, held = sizes["experts_held"]
    scores = jax.nn.sigmoid(h @ w["w_router"])
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(w["b_router"]), sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + sizes["router_eps"])
    picked = picked * sizes["routed_scaling_factor"]

    @jax.checkpoint
    def add_expert(total, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return total + weight * swiglu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (first + jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return routed


def layer(x, w, sizes):
    eps = sizes["norm_eps"]
    h = rms_norm(x, w["operator_norm"], eps)
    x = x + (short_conv(h, w) if "w_in" in w else attention(h, w, sizes))
    h = rms_norm(x, w["ffn_norm"], eps)
    if "w_1" in w:
        return x + swiglu(h, w["w_1"], w["w_3"], w["w_2"])
    return x + expert_mlp(h, w, sizes)


def mean_cross_entropy(x, norm, emb, targets, eps):
    """Mean over all rows but each sequence's last of the cross entropy of
    ``norm(x) @ emb^T`` against ``targets``, ``HEAD_ROWS`` rows of logits at a
    time."""
    b, t, h = x.shape
    counted = (jnp.arange(t) < t - 1)[None, :] & jnp.ones((b, 1), bool)
    rows = min(HEAD_ROWS, b * t)
    if (b * t) % rows:
        raise ValueError(f"{b * t} rows do not divide into head blocks of {rows}")

    @jax.checkpoint
    def block_sum(args):
        x_blk, target, keep = args
        logp = jax.nn.log_softmax(rms_norm(x_blk, norm, eps) @ emb.T)
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
    for w in params["layers"]:
        x = jax.checkpoint(lambda x, w: layer(x, w, sizes))(x, w)
    return mean_cross_entropy(
        x, params["final_norm"], params["emb"], jnp.roll(ids, -1, axis=1), sizes["norm_eps"])
