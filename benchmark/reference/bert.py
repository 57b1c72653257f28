"""Plain reference for BERT masked-LM pre-training: forward pass and loss in
straightforward ``jax.numpy``, float32, written from arXiv:1810.04805 and the
encoder of arXiv:1706.03762.  It imports nothing of ``bagua_tpu``.

Departures from the paper, each because the job that is benchmarked has it
(``configs/bert-large.json`` lists them under ``departures``): no segment
embedding and no next-sentence head (the synthetic job feeds token ids
only), no dropout, the loss averaged over *every* position (the reference
benchmark's synthetic labels), the output decoder not tied to the word
embedding, GELU in the tanh form of the original code
(google-research/bert ``modeling.py``).

The layers are stacked and scanned so that the whole model compiles as one
layer does; each layer is rematerialised in the backward pass so that the
reference fits beside nothing larger than its own parameters.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02  # the paper's truncated normal(0.02); plain normal here


def init_params(key, sizes):
    """Seeded float32 parameters.  Every leaf is random (layer-norm scales
    around one), so that no gradient the check compares is zero by
    construction."""
    h, i, v = sizes["hidden_size"], sizes["intermediate_size"], sizes["vocab_size"]
    n, p = sizes["num_hidden_layers"], sizes["max_position_embeddings"]
    shapes = {
        "word_emb": (v, h), "pos_emb": (p, h), "emb_ln_g": (h,), "emb_ln_b": (h,),
        "layers": {
            "wq": (n, h, h), "bq": (n, h), "wk": (n, h, h), "bk": (n, h),
            "wv": (n, h, h), "bv": (n, h), "wo": (n, h, h), "bo": (n, h),
            "ln1_g": (n, h), "ln1_b": (n, h),
            "w1": (n, h, i), "b1": (n, i), "w2": (n, i, h), "b2": (n, h),
            "ln2_g": (n, h), "ln2_b": (n, h),
        },
        "head_w": (h, h), "head_b": (h,), "head_ln_g": (h,), "head_ln_b": (h,),
        "dec_w": (h, v), "dec_b": (v,),
    }
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    params = jax.tree.unflatten(treedef, [
        INIT_STD * jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, leaves)
    ])
    for name in ("emb_ln_g", "head_ln_g"):
        params[name] = 1.0 + params[name]
    for name in ("ln1_g", "ln2_g"):
        params["layers"][name] = 1.0 + params["layers"][name]
    return params


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def encoder_layer(x, w, heads, eps):
    b, s, h = x.shape
    d = h // heads

    def split(y):
        return y.reshape(b, s, heads, d)

    q, k, v = (split(x @ w["w" + n] + w["b" + n]) for n in "qkv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = layer_norm(x + ctx.reshape(b, s, h) @ w["wo"] + w["bo"], w["ln1_g"], w["ln1_b"], eps)
    ffn = gelu(x @ w["w1"] + w["b1"]) @ w["w2"] + w["b2"]
    return layer_norm(x + ffn, w["ln2_g"], w["ln2_b"], eps)


def loss(params, batch, sizes):
    """Mean cross entropy of the masked-LM head over all positions."""
    ids, labels = batch
    eps, heads = sizes["layer_norm_eps"], sizes["num_attention_heads"]
    x = params["word_emb"][ids] + params["pos_emb"][: ids.shape[1]]
    x = layer_norm(x, params["emb_ln_g"], params["emb_ln_b"], eps)

    @jax.checkpoint
    def body(x, w):
        return encoder_layer(x, w, heads, eps), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    t = gelu(x @ params["head_w"] + params["head_b"])
    t = layer_norm(t, params["head_ln_g"], params["head_ln_b"], eps)
    logits = t @ params["dec_w"] + params["dec_b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
