"""Plain reference for Ouro-2.6B (``model_type`` ``ouro``, the looped language
models of ByteDance Seed, "Scaling Latent Reasoning via Looped Language
Models") causal-LM training: forward pass and loss in straightforward
``jax.numpy``, float32, written from the published ``config.json`` and the
family's description.  It imports nothing of ``bagua_tpu``.

Layer ``n`` of the stack, on ``x (batch, positions, hidden)`` (RMSNorm with a
learned scale; no bias; four norms a layer, two of them *after* the mixer and
the MLP):

* ``a = norm_in(x)``; ``q = a W_q``, ``k = a W_k``, ``v = a W_v`` in
  ``num_attention_heads`` heads of ``head_dim`` columns (as many key-value
  heads as query heads); the rotary embedding on all columns of ``q`` and
  ``k``, column ``i`` paired with ``i + size / 2``, positions ``0 .. T - 1``;
  ``ctx = softmax(q k^T / sqrt(size) + causal) v``; ``x1 = x + norm_in2(ctx
  W_o)``.
* ``m = norm_post(x1)``; ``x2 = x1 + norm_post2(W_down(silu(W_gate m) * (W_up
  m)))``.

The model: ``h_0 = E[ids]``; for ``t = 1 .. total_ut_steps``: ``h_t =
norm_f(Layers(h_{t-1}))``, the same layers and the same ``norm_f`` in every
pass, the normed state being what the next pass reads; ``logits_t = h_t
W_head``; ``lambda_t = sigmoid(h_t . w_exit + b_exit)``.  Exit distribution a
position: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t <
total_ut_steps`` and the rest of the mass on the last pass.  Loss: the mean
over each sequence's ``positions - 1`` targets of ``sum_t p_t CE_t - beta
H(p)``, ``CE_t`` the next-token cross entropy of ``logits_t`` and ``H`` the
entropy of the distribution over the passes.

For size only, never for the arithmetic: each application of a layer and
each exit are rebuilt in the backward pass, *when the backward pass reaches
them* (:func:`rebuilt_in_its_turn`), attention takes the queries in blocks
(each against all keys under the explicit mask), and each exit's head and
cross entropy take ``HEAD_ROWS`` rows at a time, rematerialised, so that no
array of all positions by the whole vocabulary outlives its block.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 256
HEAD_ROWS = 1024
#: the exit gate's bias is drawn around this: sigmoid(-0.6) = 0.35, which
#: puts about 0.35, 0.23, 0.15 and 0.27 of the mass on the four passes
EXIT_BIAS = -0.6


def rebuilt_in_its_turn(f):
    """``f(x, *weights)`` keeping only its arguments for the backward pass,
    which runs ``f`` again, as ``jax.checkpoint(f)`` does, but one call at a
    time: not before the cotangent of its result has arrived, and with
    nothing upstream started before all its gradients are whole.  A plain
    ``jax.checkpoint`` leaves the compiler free to rebuild a call's
    activations as soon as the backward pass begins (they depend on kept
    arguments alone) and to put off a call's weight gradients, which hold its
    activations: compiled for the chip, several applications' float32 arrays
    stood at once, 9.1 GB of temporaries where these two barriers leave 5.1
    (``PERF.md`` section 6, PR 42).  Values and gradients are ``f``'s own."""

    @jax.custom_vjp
    def g(x, *weights):
        return f(x, *weights)

    def forward(x, *weights):
        return f(x, *weights), (x, weights)

    def backward(kept, cotangent):
        x, weights = kept
        x, cotangent = jax.lax.optimization_barrier((x, cotangent))
        # and nothing upstream starts before every gradient of this call is whole
        return jax.lax.optimization_barrier(jax.vjp(f, x, *weights)[1](cotangent))

    g.defvjp(forward, backward)
    return g


def init_params(key, sizes):
    """Seeded float32 parameters: the embedding normal(0, 1), so that the
    first pass reads a stream of the size every later pass reads (``norm_f``
    puts it there); every matrix normal(0, 0.02), the two that write into the
    residual stream (``w_o``, ``w_down``) too: each is followed by a norm of
    its own, which undoes any scale, so the depth-scaled initialisation that
    ``reference/smallthinker_moe.py`` argues has nothing to act on here, and
    neither the published depth nor the four passes count in it; norm scales
    around one; the exit gate's weights normal(0, 0.02) and its bias normal(
    ``EXIT_BIAS``, 0.1): the four passes' mean shares are unequal and none is
    under a tenth, so a program that drops or misweights a pass is seen.  No
    compared gradient is zero or vanishing by construction."""
    h, v, width = sizes["hidden_size"], sizes["vocab_size"], sizes["intermediate_size"]
    heads, size = sizes["num_attention_heads"], sizes["head_dim"]
    layer = {
        "norm_in": (h,), "norm_in2": (h,), "norm_post": (h,), "norm_post2": (h,),
        "w_q": (h, heads * size), "w_k": (h, heads * size), "w_v": (h, heads * size),
        "w_o": (heads * size, h),
        "w_gate": (h, width), "w_up": (h, width), "w_down": (width, h),
    }
    shapes = {"emb": (v, h), "final_norm": (h,), "w_head": (h, v), "w_exit": (h,), "b_exit": (),
              "layers": [dict(layer) for _ in range(sizes["num_hidden_layers"])]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))

    def leaf(k, path, shape):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if "emb" in name:
            return noise
        if "b_exit" in name:
            return EXIT_BIAS + 0.1 * noise
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


def attention(a, w, sizes):
    b, t, _ = a.shape
    heads, size = sizes["num_attention_heads"], sizes["head_dim"]
    q = rotary((a @ w["w_q"]).reshape(b, t, heads, size), sizes["rope_theta"])
    k = rotary((a @ w["w_k"]).reshape(b, t, heads, size), sizes["rope_theta"])
    v = (a @ w["w_v"]).reshape(b, t, heads, size)
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions do not divide into query blocks of {block}")

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(size)
        seen = first + jnp.arange(block)[:, None] >= jnp.arange(t)[None, :]
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)

    q_blocks = q.reshape(b, t // block, block, heads, size).swapaxes(0, 1)
    ctx = jax.lax.map(one_block, (q_blocks, jnp.arange(0, t, block)))
    return ctx.swapaxes(0, 1).reshape(b, t, heads * size) @ w["w_o"]


def layer(x, w, sizes):
    eps = sizes["rms_norm_eps"]
    x = x + rms_norm(attention(rms_norm(x, w["norm_in"], eps), w, sizes), w["norm_in2"], eps)
    m = rms_norm(x, w["norm_post"], eps)
    mlp = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    return x + rms_norm(mlp, w["norm_post2"], eps)


def cross_entropy(h, head, targets):
    """``(h, -log_softmax(h @ head)[target] of every row)`` for ``h (batch,
    positions, hidden)``, ``HEAD_ROWS`` rows of logits at a time; ``h`` is
    handed back so that what reads it next waits for this exit."""
    b, t, width = h.shape
    rows = min(HEAD_ROWS, b * t)
    if (b * t) % rows:
        raise ValueError(f"{b * t} rows do not divide into head blocks of {rows}")

    @jax.checkpoint
    def block(args):
        h_blk, target = args
        logp = jax.nn.log_softmax(h_blk @ head)
        return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]

    return h, jax.lax.map(
        block, (h.reshape(-1, rows, width), targets.reshape(-1, rows))).reshape(b, t)


def exit_distribution(gates):
    """``gates``: the passes' ``lambda_t``, each ``(batch, positions)``.  A
    pass takes its gate's share of what the passes before it left; the last
    takes all that is left, whatever its gate says."""
    left, shares = jnp.ones_like(gates[0]), []
    for gate in gates[:-1]:
        shares.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(shares + [left])


def loss(params, batch, sizes):
    """Mean over each sequence's ``positions - 1`` targets of the passes'
    cross entropies weighted by the exit distribution, less ``beta`` times the
    distribution's entropy."""
    ids = batch
    targets = jnp.roll(ids, -1, axis=1)
    h = params["emb"][ids]
    entropies, gates = [], []
    one_layer = rebuilt_in_its_turn(lambda x, w: layer(x, w, sizes))
    one_exit = rebuilt_in_its_turn(lambda h, head: cross_entropy(h, head, targets))
    for _ in range(sizes["total_ut_steps"]):
        for w in params["layers"]:
            h = one_layer(h, w)
        h, entropy = one_exit(rms_norm(h, params["final_norm"], sizes["rms_norm_eps"]),
                              params["w_head"])
        entropies.append(entropy)
        gates.append(jax.nn.sigmoid(h @ params["w_exit"] + params["b_exit"]))
    p = exit_distribution(gates)
    spread = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    per_position = jnp.sum(p * jnp.stack(entropies), axis=0) - sizes["entropy_beta"] * spread
    return jnp.mean(per_position[:, :-1])
