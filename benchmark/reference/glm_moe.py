"""Plain reference for GLM-4.7-Flash (``glm4_moe_lite``) causal-LM training:
forward pass and loss in straightforward ``jax.numpy``, float32, written from
the published ``config.json`` and the family's descriptions (DeepSeek-V2
section 2.1 for latent attention, DeepSeek-V3 sections 2.1.2 and 2.2 for the
sigmoid router with its selection bias and for multi-token prediction).  It
imports nothing of ``bagua_tpu``.

The layers (``x`` the residual stream; RMSNorm with a learned scale; no bias):

* attention: ``c_q = norm(x W_dq)``, ``q = c_q W_uq`` per head ``[q_nope |
  q_rope]``; ``[c_kv | k_rope] = x W_dkv``, ``[k_nope | v] = norm(c_kv) W_ukv``;
  rotary embedding on ``q_rope`` and on the one ``k_rope`` all heads share;
  ``softmax(q k^T / sqrt(nope + rope) + causal) v``; ``W_o``.
* dense layer: ``x + W_down(silu(W_gate h) * W_up h)``.
* expert layer: ``s = sigmoid(h W_r)``; the ``k`` experts of largest ``s + b``;
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
  ``sum_chosen w_i E_i(h) + E_shared(h)``.  Given a *share* (``experts_held``)
  it adds the terms of the chosen experts in that range only, the weights
  still normalised over all ``k``: what one of the chips that divide the
  layer's experts computes.  No sort: each held expert is applied to every
  token under its weight, zero where it was not chosen.
* multi-token prediction, depth 1: ``h' = W_eh [norm(Emb(t_{i+1})) |
  norm(x_i)]``, one expert layer, a norm of its own, the same embedding and
  output matrices, cross-entropy against ``t_{i+2}`` with weight ``lambda``.

Departures, each listed in ``configs/glm-4.7-flash.json``: the rotary
embedding rotates interleaved pairs ``(2i, 2i+1)`` (the published code's
layout of the same rotation differs by a fixed permutation of ``W_uq`` and
``W_dkv`` columns); ``b`` is seeded and fixed; ``lambda`` is assumed.

For size only, never for the arithmetic: each layer is rematerialised in the
backward pass, attention takes the queries in blocks (each against all keys
under the causal mask), the held experts and the rows of the head are taken
one after the other.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 512
HEAD_ROWS = 1024


def init_params(key, sizes):
    """Seeded float32 parameters: every leaf normal(0, 0.02), norm scales
    around one, so that no compared gradient is zero by construction (the
    router's selection bias ``b`` takes none by definition)."""
    h, v = sizes["hidden_size"], sizes["vocab_size"]
    heads, nope, rope = sizes["num_attention_heads"], sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    qr, kvr, dv = sizes["q_lora_rank"], sizes["kv_lora_rank"], sizes["v_head_dim"]
    width, held = sizes["moe_intermediate_size"], sizes["experts_held"][1]

    def layer(dense):
        shapes = {
            "attn_norm": (h,), "w_dq": (h, qr), "q_norm": (qr,), "w_uq": (qr, heads * (nope + rope)),
            "w_dkv": (h, kvr + rope), "kv_norm": (kvr,), "w_ukv": (kvr, heads * (nope + dv)),
            "w_o": (heads * dv, h), "mlp_norm": (h,),
        }
        if dense:
            i = sizes["intermediate_size"]
            shapes.update(w_gate=(h, i), w_up=(h, i), w_down=(i, h))
        else:
            shared = width * sizes["n_shared_experts"]
            shapes.update(
                w_router=(h, sizes["routed_experts_total"]), b_router=(sizes["routed_experts_total"],),
                e_gate=(held, h, width), e_up=(held, h, width), e_down=(held, width, h),
                s_gate=(h, shared), s_up=(h, shared), s_down=(shared, h))
        return shapes

    dense_layers = sizes["first_k_dense_replace"]
    shapes = {
        "emb": (v, h), "head": (h, v), "final_norm": (h,),
        "layers": [layer(n < dense_layers) for n in range(sizes["num_hidden_layers"])],
    }
    if sizes["num_nextn_predict_layers"]:
        shapes["mtp"] = {"emb_norm": (h,), "hidden_norm": (h,), "w_eh": (2 * h, h),
                         "layer": layer(False), "final_norm": (h,)}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    return jax.tree.unflatten(treedef, [
        ("norm" in jax.tree_util.keystr(path)) + INIT_STD * jax.random.normal(k, shape, jnp.float32)
        for k, (path, shape) in zip(keys, flat)])


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotary(x, theta):
    """``x`` (batch, positions, heads, size): pair ``(2i, 2i+1)`` turned by
    ``position * theta ** (-2i / size)``."""
    size = x.shape[-1]
    freq = theta ** (-jnp.arange(0, size, 2, dtype=jnp.float32) / size)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def attention(x, w, sizes):
    b, t, _ = x.shape
    heads, nope, rope = sizes["num_attention_heads"], sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    kvr, dv, eps = sizes["kv_lora_rank"], sizes["v_head_dim"], sizes["rms_norm_eps"]
    q = (rms_norm(x @ w["w_dq"], w["q_norm"], eps) @ w["w_uq"]).reshape(b, t, heads, nope + rope)
    down = x @ w["w_dkv"]
    kv = (rms_norm(down[..., :kvr], w["kv_norm"], eps) @ w["w_ukv"]).reshape(b, t, heads, nope + dv)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], sizes["rope_theta"])], axis=-1)
    k_rope = rotary(down[..., None, kvr:], sizes["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope))], axis=-1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions do not divide into query blocks of {block}")

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(nope + rope)
        rows = first + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    q_blocks = q.reshape(b, t // block, block, heads, nope + rope).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    return ctx.swapaxes(0, 1).reshape(b, t, heads * dv) @ w["w_o"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def expert_mlp(h, w, sizes):
    first, held = sizes["experts_held"]
    scores = jax.nn.sigmoid(h @ w["w_router"])
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(w["b_router"]), sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * sizes["routed_scaling_factor"]

    @jax.checkpoint
    def add_expert(total, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1, keepdims=True)
        return total + weight * swiglu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h),
        (first + jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return routed + swiglu(h, w["s_gate"], w["s_up"], w["s_down"])


def layer(x, w, sizes):
    eps = sizes["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"], eps), w, sizes)
    h = rms_norm(x, w["mlp_norm"], eps)
    if "w_gate" in w:
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + expert_mlp(h, w, sizes)


def mean_cross_entropy(x, norm, head, targets, skip_last, eps):
    """Mean over all rows but each sequence's last ``skip_last`` of the cross
    entropy of ``norm(x) @ head`` against ``targets``, ``HEAD_ROWS`` rows of
    logits at a time."""
    b, t, h = x.shape
    counted = (jnp.arange(t) < t - skip_last)[None, :] & jnp.ones((b, 1), bool)
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
    return jnp.sum(sums) / (b * (t - skip_last))


def loss(params, batch, sizes):
    """Next-token cross entropy, mean over each sequence's ``positions - 1``
    targets; with the prediction module, plus ``lambda`` times the mean cross
    entropy of the token after next."""
    ids, eps = batch, sizes["rms_norm_eps"]
    x = params["emb"][ids]
    for w in params["layers"]:
        x = jax.checkpoint(lambda x, w: layer(x, w, sizes))(x, w)
    total = mean_cross_entropy(
        x, params["final_norm"], params["head"], jnp.roll(ids, -1, axis=1), 1, eps)
    if "mtp" in params:
        m = params["mtp"]
        after = params["emb"][jnp.roll(ids, -1, axis=1)]
        joined = jnp.concatenate(
            [rms_norm(after, m["emb_norm"], eps), rms_norm(x, m["hidden_norm"], eps)], axis=-1)
        y = jax.checkpoint(lambda y, w: layer(y, w, sizes))(joined @ m["w_eh"], m["layer"])
        total = total + sizes["mtp_loss_weight"] * mean_cross_entropy(
            y, m["final_norm"], params["head"], jnp.roll(ids, -2, axis=1), 2, eps)
    return total
