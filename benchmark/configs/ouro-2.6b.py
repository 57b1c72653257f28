"""Adapter for ``ouro-2.6b``: builds the program's model and loss through
``bagua_tpu.models.ouro``, maps the benchmark's seeded weights (in the layout
of ``reference/ouro.py``) onto the program's parameter tree, draws a batch
from the whole vocabulary, and counts operations: of one sample's training
step, and of the three parts whose share of the chip's peak the benchmark
reports.  Every count takes each of the ``layers x passes`` applications of a
layer and each of the ``passes`` exits once, forward and backward: nothing the
program's memory plan runs again is counted, so no share can pass 100%
whatever is recomputed."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
    "use_sliding_window", "tie_word_embeddings", "total_ut_steps", "vocab_size",
)


def sizes(config, traffic_input):
    out = {k: config[k] for k in KEYS}
    out["layer_types"] = tuple(out["layer_types"])
    out["entropy_beta"] = config["assumed"]["entropy_beta"]
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.ouro import HF_KEYS, OuroConfig

    return OuroConfig(**{k: sz[k] for k in HF_KEYS}, entropy_beta=sz["entropy_beta"],
                      compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.ouro import OuroModel, ouro_loss_fn

    return ouro_loss_fn(OuroModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's ``OuroBlock``."""
    return {
        "input_norm": {"scale": w["norm_in"]}, "input_norm_2": {"scale": w["norm_in2"]},
        "post_attention_norm": {"scale": w["norm_post"]},
        "post_attention_norm_2": {"scale": w["norm_post2"]},
        "attn": {"q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"], "out_proj": w["w_o"]},
        "mlp": {"gate": w["w_gate"], "up": w["w_up"], "down": w["w_down"]},
    }


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout (parameters, gradients or updates),
    rearranged into the program's parameter tree.  Every leaf is float32 on
    both sides, so ``cast`` changes nothing."""
    del sz, cast
    out = {"embedding": tree["emb"], "final_norm": {"scale": tree["final_norm"]},
           "lm_head": tree["w_head"], "exit_gate": tree["w_exit"],
           "exit_gate_bias": tree["b_exit"]}
    for n, w in enumerate(tree["layers"]):
        out[f"layer_{n}"] = _block(w)
    return out


def draw_batch(key, n, sz):
    """``n`` sequences of uniform random token ids from the whole vocabulary;
    the targets are the same ids, shifted by the loss."""
    return jax.random.randint(key, (n, sz["seq_len"]), 0, sz["vocab_size"], jnp.int32)


def layer_applications(sz) -> int:
    """How often a layer runs in one forward pass: every layer of the stack
    in every pass."""
    return sz["num_hidden_layers"] * sz["total_ut_steps"]


def _part_counts(sz):
    """Multiply-adds of one sequence's forward pass through *one* application
    of a layer, by part, and through one exit's head: the four projections,
    the core over every pair at or under the diagonal (scores and mixing), the
    three products of the MLP."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads, kv_heads, size = sz["num_attention_heads"], sz["num_key_value_heads"], sz["head_dim"]
    return {
        "attn_proj": s * (2 * h * heads * size + 2 * h * kv_heads * size),
        "attn_core": heads * 2 * size * (s * (s + 1) // 2),
        "dense_mlp": s * 3 * h * sz["intermediate_size"],
        "head": s * h * sz["vocab_size"],
    }


def layer_products_flops_per_sample(sz):
    """Operations of the layers' seven products (four projections, three of
    the MLP) in one sequence's step: 2 per multiply-add, forward and backward,
    every application once."""
    c = _part_counts(sz)
    return 3.0 * 2.0 * (c["attn_proj"] + c["dense_mlp"]) * layer_applications(sz)


def attention_core_flops_per_sample(sz):
    """Operations of the attention cores (scores and mixing over the causal
    half, forward and backward, recomputation not counted): per application
    ``3 x 2 x 2 x heads x 128 x s (s + 1) / 2``."""
    return 3.0 * 2.0 * _part_counts(sz)["attn_core"] * layer_applications(sz)


def head_flops_per_sample(sz):
    """Operations of the exits' head products: ``3 x 2 x s x hidden x vocab``
    an exit, one exit a pass."""
    return 3.0 * 2.0 * _part_counts(sz)["head"] * sz["total_ut_steps"]


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward: every application of a layer and
    every exit's head once.  Nothing recomputed, nothing elementwise (the
    gate's one column is 0.0005% of an exit's head and is left out)."""
    return (layer_products_flops_per_sample(sz) + attention_core_flops_per_sample(sz)
            + head_flops_per_sample(sz))
