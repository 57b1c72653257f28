"""Plain reference for SmallThinker-21BA3B-Instruct (``smallthinker``,
arXiv:2507.20984) causal-LM training: forward pass and loss in straightforward
``jax.numpy``, float32, written from the published ``config.json`` and the
family's description.  It imports nothing of ``bagua_tpu``.

Layer ``n`` (``x`` the residual stream; RMSNorm with a learned scale; no bias,
no norm on heads):

* ``h = norm_in(x)``.
* router, before attention, from ``h``: ``l = h W_r``; the ``k`` experts of
  largest ``l``; ``w = softmax(l[chosen])`` over those ``k`` (their sum is one,
  so ``norm_topk_prob`` changes nothing).
* attention from ``h``: ``q = h W_q`` in ``num_attention_heads`` heads, ``k = h
  W_k`` and ``v = h W_v`` in ``num_key_value_heads``, of ``head_dim`` columns;
  where ``rope_layout[n]`` is 1 the rotary embedding on all columns of ``q`` and
  ``k``, column ``i`` paired with ``i + size / 2``, where it is 0 nothing; each
  key-value head repeated for its group of query heads; ``softmax(q k^T /
  sqrt(size) + mask) v`` with key ``j`` open to position ``i`` where ``i >= j``
  and, where ``sliding_window_layout[n]`` is 1, ``i - j < sliding_window_size``;
  ``x1 = x + ctx W_o``.
* experts: ``h2 = norm_post(x1)``; ``x2 = x1 + sum_chosen w_i E_i(h2)``, ``E(u)
  = W_down(relu(W_gate u) * W_up u)``; no shared expert, no dense layer.  Given
  a *share* (``experts_held``) it adds the terms of the chosen experts in that
  range only, the weights still a softmax over all ``k``: what one of the chips
  that divide the layer's experts computes.  No sort: each held expert is
  applied to every token under its weight, zero where it was not chosen.
* head: ``norm(x) W_head``, a matrix of its own; next-token cross entropy, mean
  over the sequence's targets.

Assumed, each listed in ``configs/smallthinker-21ba3b.json``: the router reads
the attention's normed input, the window counts the current position.

For size only, never for the arithmetic: each layer is rematerialised in the
backward pass, attention takes the queries in blocks (each against all keys
under the explicit mask), the held experts and the rows of the head are taken
one after the other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 256
HEAD_ROWS = 1024


def init_params(key, sizes):
    """Seeded float32 parameters: the embedding normal(0, 1), matrices
    normal(0, 0.02), the two that write into the residual stream (``w_o``, the
    experts' ``e_down``) normal(0, 0.02 / sqrt(2 x the published depth)), norm
    scales around one: no compared gradient is zero or vanishing by
    construction.  The stream so starts at the size the norms put it to and
    stays a token's own through the depth, and every router's load stays near
    its expectation, as a trained model's auxiliary loss keeps it: with all of
    them at 0.02 this model's stream is the attention's running mean of values,
    all but the same for neighbouring tokens, the routers of layers 1 to 3
    send nearly every token to a few experts, and the rows this share gets in
    a layer range from 0.03 to 1.9 times their expectation by seed (``PERF.md``
    section 6, PR 36)."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, kv_heads, size = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                             sizes["head_dim"])
    width, held, total = (sizes["moe_ffn_hidden_size"], sizes["experts_held"][1],
                          sizes["routed_experts_total"])
    layer = {
        "norm_in": (h,), "norm_post": (h,), "w_router": (h, total),
        "w_q": (h, heads * size), "w_k": (h, kv_heads * size), "w_v": (h, kv_heads * size),
        "w_o": (heads * size, h),
        "e_gate": (held, h, width), "e_up": (held, h, width), "e_down": (held, width, h),
    }
    shapes = {"emb": (v, h), "final_norm": (h,), "w_head": (h, v),
              "layers": [dict(layer) for _ in range(sizes["num_hidden_layers"])]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))

    residual_std = INIT_STD / math.sqrt(2 * sizes["published_layers"])

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "emb" in name:
            return noise
        if "w_o" in name or "e_down" in name:
            return residual_std * noise
        return ("norm" in name) + INIT_STD * noise

    return jax.tree.unflatten(
        treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotary(x, theta):
    """``x`` (batch, positions, heads, size): column ``i`` and column ``i +
    size / 2`` turned by ``position * theta ** (-2i / size)``."""
    size = x.shape[-1]
    freq = theta ** (-jnp.arange(0, size, 2, dtype=jnp.float32) / size)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :size // 2], x[..., size // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def attention(h, w, sizes, windowed: bool, positions: bool):
    b, t, _ = h.shape
    heads, kv_heads, size = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                             sizes["head_dim"])
    window = sizes["sliding_window_size"]
    q = (h @ w["w_q"]).reshape(b, t, heads, size)
    k = (h @ w["w_k"]).reshape(b, t, kv_heads, size)
    v = (h @ w["w_v"]).reshape(b, t, kv_heads, size)
    if positions:
        q, k = rotary(q, sizes["rope_theta"]), rotary(k, sizes["rope_theta"])
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
        seen = (i >= j) & (i - j < window) if windowed else (i >= j)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = q.reshape(b, t // block, block, heads, size).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    return ctx.swapaxes(0, 1).reshape(b, t, heads * size) @ w["w_o"]


def route(h, w, sizes):
    """``(chosen (…, k), weights (…, k))``: the ``k`` largest logits and the
    softmax over them."""
    top, chosen = jax.lax.top_k(h @ w["w_router"], sizes["moe_num_active_primary_experts"])
    return chosen, jax.nn.softmax(top, axis=-1)


def relu_glu(u, gate, up, down):
    return (jax.nn.relu(u @ gate) * (u @ up)) @ down


def experts(h, chosen, picked, w, sizes):
    first, held = sizes["experts_held"]

    @jax.checkpoint
    def add_expert(total, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return total + weight * relu_glu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (first + jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return routed


def layer(x, w, sizes, windowed: bool, positions: bool):
    eps = sizes["rms_norm_eps"]
    h = rms_norm(x, w["norm_in"], eps)
    chosen, picked = route(h, w, sizes)  # before attention, from its input
    x = x + attention(h, w, sizes, windowed, positions)
    return x + experts(rms_norm(x, w["norm_post"], eps), chosen, picked, w, sizes)


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
    kinds = zip(sizes["sliding_window_layout"], sizes["rope_layout"])
    for w, (windowed, positions) in zip(params["layers"], kinds):
        x = jax.checkpoint(
            lambda x, w, windowed=bool(windowed), positions=bool(positions): layer(
                x, w, sizes, windowed, positions))(x, w)
    return mean_cross_entropy(
        x, params["final_norm"], params["w_head"], jnp.roll(ids, -1, axis=1), sizes["rms_norm_eps"])
