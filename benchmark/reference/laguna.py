"""Plain reference for Laguna-XS.2 (``laguna``, poolside) causal-LM training:
forward pass and loss in straightforward ``jax.numpy``, float32, written from
the published ``config.json`` and, for what no key states, the family's
convention (each listed under ``assumed`` in ``configs/laguna-xs.2.json``).  It
imports nothing of ``bagua_tpu``.

Layer ``n`` (``x`` the residual stream; RMSNorm with a learned scale; no bias,
no norm on heads):

1. ``h = norm_in(x)``; ``q = h W_q`` in ``H_n = num_attention_heads_per_layer[n]``
   heads, ``k = h W_k`` and ``v = h W_v`` in ``num_key_value_heads``, of
   ``head_dim`` columns; key-value head ``j`` serves query heads ``j g .. (j +
   1) g - 1``, ``g = H_n / kv heads``.
2. The rotary embedding by ``rope_parameters[layer_types[n]]``, on the first
   ``head_dim x partial_rotary_factor`` columns of ``q`` and ``k``, column
   ``i`` paired with ``i + columns / 2``; the others pass through.  ``default``:
   ``inv_freq_i = theta^(-2i / columns)``.  ``yarn``: as ``transformers``'
   ``_compute_yarn_parameters``, ``inv_freq_i = (1 - r_i) / (factor
   theta^(2i/columns)) + r_i / theta^(2i/columns)``, ``r_i = 1 - clip((i - low)
   / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``, ``high =
   ceil(c(beta_slow))``, ``c(m) = columns ln(original / (2 pi m)) / (2 ln
   theta)``; ``cos`` and ``sin`` times ``attention_factor``.
3. ``ctx = softmax(q k^T / sqrt(head_dim) + mask) v`` with key ``j`` open to
   position ``i`` where ``i >= j`` and, in a ``sliding_attention`` layer, ``i -
   j < sliding_window``.
4. The gate: ``a = sigmoid(h W_g)``, one scalar a head and position;
   ``ctx[head] *= a[head]``; ``x1 = x + ctx W_o``.
5. ``u = norm_post(x1)``.  A ``dense`` layer: ``x2 = x1 + W_down(silu(W_gate u)
   * W_up u)``.  A ``sparse`` one: ``s = sigmoid(u W_r)``; the ``k`` experts of
   largest ``s + b``; ``w = s[chosen] / (sum s[chosen] + 1e-20) *
   moe_routed_scaling_factor``; ``x2 = x1 + E_shared(u) + sum_chosen w_i
   E_i(u)``.  Given a *share* (``experts_held``) it adds the terms of the chosen
   experts in that range only, the weights still normalised over all ``k``:
   what one of the chips that divide the layer's experts computes.  No sort:
   each held expert is applied to every token under its weight, zero where it
   was not chosen.
6. Head: ``norm(x) W_head``, a matrix of its own; next-token cross entropy,
   mean over the sequence's targets.

For size only, never for the arithmetic: each layer is rematerialised in the
backward pass, attention takes the queries in blocks (each against all keys
under the explicit mask, so that 64 heads x 8,192 x 8,192 scores never stand
at once), the held experts and the rows of the head are taken one after the
other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.002
QUERY_BLOCK = 256
HEAD_ROWS = 1024
SLIDING = "sliding_attention"


def init_params(key, sizes):
    """Seeded float32 parameters: the embedding normal(0, 1), matrices
    normal(0, 0.02), those that write into the residual stream (``w_o``,
    ``w_down``, ``s_down``, ``e_down``) normal(0, 0.02 / sqrt(2 x the published
    depth)), norm scales around one, the router's selection bias normal(0,
    0.002): no compared gradient is zero or vanishing by construction (the
    bias takes none by definition).  The stream so starts at the size the norms
    put it to and stays a token's own through the depth, and every router's
    load stays near its expectation (``PERF.md`` section 6, PR 36).
    ``sizes["init_std"]`` stands in for the matrices' 0.02 where the toy sizes
    set it."""
    h, v, size = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    kv_heads = sizes["num_key_value_heads"]
    width, shared = sizes["moe_intermediate_size"], sizes["shared_expert_intermediate_size"]
    held, total = sizes["experts_held"][1], sizes["routed_experts_total"]

    def layer(heads, mlp):
        shapes = {
            "norm_in": (h,), "w_q": (h, heads * size), "w_k": (h, kv_heads * size),
            "w_v": (h, kv_heads * size), "w_g": (h, heads), "w_o": (heads * size, h),
            "norm_post": (h,),
        }
        if mlp == "dense":
            i = sizes["intermediate_size"]
            shapes.update(w_gate=(h, i), w_up=(h, i), w_down=(i, h))
        else:
            shapes.update(
                w_router=(h, total), b_router=(total,),
                e_gate=(held, h, width), e_up=(held, h, width), e_down=(held, width, h),
                s_gate=(h, shared), s_up=(h, shared), s_down=(shared, h))
        return shapes

    shapes = {"emb": (v, h), "final_norm": (h,), "w_head": (h, v),
              "layers": [layer(heads, mlp) for heads, mlp in zip(
                  sizes["num_attention_heads_per_layer"], sizes["mlp_layer_types"])]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    std = sizes.get("init_std", INIT_STD)
    residual_std = std / math.sqrt(2 * sizes["published_layers"])

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "emb" in name:
            return noise
        if "b_router" in name:
            return BIAS_STD * noise
        if "w_o" in name or "_down" in name:
            return residual_std * noise
        return ("norm" in name) + std * noise

    return jax.tree.unflatten(
        treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def inv_freq(rope, columns):
    """The turn a position of each of the ``columns / 2`` pairs, and the
    factor on ``cos`` and ``sin``: step 2 of the module's text."""
    theta = rope["rope_theta"]
    plain = theta ** (-jnp.arange(0, columns, 2, dtype=jnp.float32) / columns)
    if rope["rope_type"] == "default":
        return plain, 1.0

    def pair_of(turns):
        return columns * math.log(rope["original_max_position_embeddings"] / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), columns - 1)
    ramp = jnp.clip((jnp.arange(columns // 2, dtype=jnp.float32) - low) / max(
        high - low, 0.001), 0.0, 1.0)
    r = 1.0 - ramp
    return (1.0 - r) * plain / rope["factor"] + r * plain, rope["attention_factor"]


def rotary(x, rope):
    """``x`` (batch, positions, heads, size): of the first ``size x
    partial_rotary_factor`` columns, column ``i`` and column ``i + columns /
    2`` turned by ``position * inv_freq[i]``, ``cos`` and ``sin`` times the
    tables' factor; the other columns as they came."""
    size = x.shape[-1]
    columns = int(size * rope["partial_rotary_factor"])
    freq, factor = inv_freq(rope, columns)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = factor * jnp.cos(angle)[None, :, None, :]
    sin = factor * jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :columns // 2], x[..., columns // 2:columns]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin, x[..., columns:]], axis=-1)


def attention(h, w, sizes, kind: str):
    b, t, _ = h.shape
    kv_heads, size = sizes["num_key_value_heads"], sizes["head_dim"]
    heads = w["w_q"].shape[1] // size
    window = sizes["sliding_window"]
    q = (h @ w["w_q"]).reshape(b, t, heads, size)
    k = (h @ w["w_k"]).reshape(b, t, kv_heads, size)
    v = (h @ w["w_v"]).reshape(b, t, kv_heads, size)
    rope = sizes["rope_parameters"][kind]
    q, k = rotary(q, rope), rotary(k, rope)
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
        seen = (i >= j) & (i - j < window) if kind == SLIDING else (i >= j)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = q.reshape(b, t // block, block, heads, size).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, t, heads, size)
    if sizes["gating"]:
        ctx = ctx * jax.nn.sigmoid(h @ w["w_g"])[..., None]
    return ctx.reshape(b, t, heads * size) @ w["w_o"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(u, w, sizes):
    """``(chosen (…, k), weights (…, k))``: the ``k`` largest of ``s + b``,
    and ``s`` of those over their sum, times the scaling factor."""
    scores = jax.nn.sigmoid(u @ w["w_router"])
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(w["b_router"]), sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, picked * sizes["moe_routed_scaling_factor"]


def routed_experts(u, chosen, picked, w, sizes):
    """The held experts' part of the routed sum."""
    first, held = sizes["experts_held"]

    @jax.checkpoint
    def one_expert(expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return weight * swiglu(u, gate, up, down)

    routed, _ = jax.lax.scan(
        lambda total, expert: (total + one_expert(expert), None), jnp.zeros_like(u),
        (first + jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return routed


def mlp(u, w, sizes):
    if "w_gate" in w:
        return swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    chosen, picked = route(u, w, sizes)
    return (routed_experts(u, chosen, picked, w, sizes)
            + swiglu(u, w["s_gate"], w["s_up"], w["s_down"]))


def layer(x, w, sizes, kind: str):
    eps = sizes["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["norm_in"], eps), w, sizes, kind)
    return x + mlp(rms_norm(x, w["norm_post"], eps), w, sizes)


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
    for w, kind in zip(params["layers"], sizes["layer_types"]):
        x = jax.checkpoint(lambda x, w, kind=kind: layer(x, w, sizes, kind))(x, w)
    return mean_cross_entropy(
        x, params["final_norm"], params["w_head"], jnp.roll(ids, -1, axis=1), sizes["rms_norm_eps"])
