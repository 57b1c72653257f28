"""Adapter for ``bert-large``: builds the program's model and loss through
``bagua_tpu.models``, maps the benchmark's seeded weights (in the layout of
``reference/bert.py``) onto the program's parameter tree, draws a batch, and
counts the operations of one sample."""

import jax
import jax.numpy as jnp

#: leaves of the reference's ``layers`` group that the program stores in
#: bfloat16 (its dense kernels and biases); everything else is float32
#: the leaf nearest the loss, in the program's tree
HEAD_LEAF = "['mlm_decoder']['kernel']"
BF16_LAYER_LEAVES = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo", "w1", "b1", "w2", "b2")


def sizes(config, traffic_input):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "intermediate_size", "vocab_size", "max_position_embeddings", "layer_norm_eps")
    out = {k: config[k] for k in keys}
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > out["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds the position table")
    return out


def build_loss(sz):
    from bagua_tpu.models.bert import BertConfig, BertForPreTraining, mlm_loss_fn

    cfg = BertConfig(
        vocab_size=sz["vocab_size"], hidden_size=sz["hidden_size"],
        num_layers=sz["num_hidden_layers"], num_heads=sz["num_attention_heads"],
        intermediate_size=sz["intermediate_size"],
        max_position_embeddings=sz["max_position_embeddings"],
        layer_norm_eps=sz["layer_norm_eps"], compute_dtype=jnp.bfloat16,
    )
    return mlm_loss_fn(BertForPreTraining(cfg))


def as_stored(ref_params):
    """The reference's float32 parameters holding exactly the values the
    program holds: what it stores in bfloat16 is rounded through it."""
    layers = dict(ref_params["layers"])
    for k in BF16_LAYER_LEAVES:
        # bfloat16's rounding as one operation: a cast there and back is a
        # pair XLA may drop (``xla_allow_excess_precision``)
        layers[k] = jax.lax.reduce_precision(layers[k], exponent_bits=8, mantissa_bits=7)
    return {**ref_params, "layers": layers}


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout (parameters, gradients or updates),
    rearranged into the program's parameter tree.  With ``cast`` the leaves
    take the program's storage types."""
    lay = tree["layers"]
    dense = (lambda x: x.astype(jnp.bfloat16)) if cast else (lambda x: x)

    def ln(g, b):
        return {"scale": g, "bias": b}

    bert = {
        "word_embeddings": {"embedding": tree["word_emb"]},
        "position_embeddings": {"embedding": tree["pos_emb"]},
        "ln_embed": ln(tree["emb_ln_g"], tree["emb_ln_b"]),
    }
    for n in range(sz["num_hidden_layers"]):
        bert[f"layer_{n}"] = {
            "attention": {
                # columns ordered (q | k | v), each head-major: the program
                # reshapes its projection to (batch, seq, 3, heads, head_dim)
                "qkv": {
                    "kernel": dense(jnp.concatenate(
                        [lay["wq"][n], lay["wk"][n], lay["wv"][n]], axis=1)),
                    "bias": dense(jnp.concatenate(
                        [lay["bq"][n], lay["bk"][n], lay["bv"][n]])),
                },
                "out": {"kernel": dense(lay["wo"][n]), "bias": dense(lay["bo"][n])},
            },
            "ln_attn": ln(lay["ln1_g"][n], lay["ln1_b"][n]),
            "mlp": {
                "ColumnParallelDense_0": {"kernel": dense(lay["w1"][n]), "bias": dense(lay["b1"][n])},
                "RowParallelDense_0": {"kernel": dense(lay["w2"][n]), "bias": dense(lay["b2"][n])},
            },
            "ln_ffn": ln(lay["ln2_g"][n], lay["ln2_b"][n]),
        }
    return {
        "bert": bert,
        "mlm_transform": {"kernel": tree["head_w"], "bias": tree["head_b"]},
        "mlm_ln": ln(tree["head_ln_g"], tree["head_ln_b"]),
        "mlm_decoder": {"kernel": tree["dec_w"], "bias": tree["dec_b"]},
    }


def draw_batch(key, n, sz):
    """``n`` sequences of uniform random token ids, and as many labels."""
    k_ids, k_labels = jax.random.split(key)
    shape = (n, sz["seq_len"])
    return (jax.random.randint(k_ids, shape, 0, sz["vocab_size"], jnp.int32),
            jax.random.randint(k_labels, shape, 0, sz["vocab_size"], jnp.int32))


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications (projections, attention scores and
    mixing, feed-forward, masked-LM transform and decoder) at two operations
    per multiply-add, times three for forward and backward.  Nothing
    recomputed, nothing elementwise."""
    s, h, i = sz["seq_len"], sz["hidden_size"], sz["intermediate_size"]
    layer = 2 * s * h * 3 * h + 2 * 2 * s * s * h + 2 * s * h * h + 2 * 2 * s * h * i
    head = 2 * s * h * h + 2 * s * h * sz["vocab_size"]
    return 3.0 * (sz["num_hidden_layers"] * layer + head)
