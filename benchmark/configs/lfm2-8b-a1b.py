"""Adapter for ``lfm2-8b-a1b``: builds the program's model and loss through
``bagua_tpu.models.lfm2_moe``, maps the benchmark's seeded weights (in the
layout of ``reference/lfm2_moe.py``) onto the program's parameter tree, draws
a batch from the vocabulary slice, and counts operations: of one sample's
training step, and of the two parts whose share of the chip's peak the
benchmark reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the embedding, which is
#: the output matrix too
HEAD_LEAF = "['embedding']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_dense_layers", "layer_types", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "conv_bias", "num_experts_per_tok", "routed_scaling_factor",
    "norm_topk_prob", "use_expert_bias", "rope_theta", "norm_eps", "vocab_size", "router_eps",
)


def sizes(config, traffic_input):
    """The file's ``num_experts`` counts the experts *held here*; the router
    keeps the published width (``published.num_experts``), and
    ``deployment.share_held`` says which of the equal shares this chip is."""
    out = {k: config[k] for k in KEYS}
    out["layer_types"] = tuple(out["layer_types"])
    held, total = config["num_experts"], config["published"]["num_experts"]
    if total % held:
        raise ValueError(f"{total} routed experts do not divide into shares of {held}")
    out["routed_experts_total"] = total
    out["experts_held"] = (config["deployment"]["share_held"] * held, held)
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.lfm2_moe import HF_KEYS, Lfm2MoeConfig

    return Lfm2MoeConfig(
        **{k: sz[k] for k in HF_KEYS if k in sz}, num_experts=sz["routed_experts_total"],
        experts_held=sz["experts_held"], router_eps=sz["router_eps"],
        compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.lfm2_moe import Lfm2MoeModel, lfm2_moe_loss_fn

    return lfm2_moe_loss_fn(Lfm2MoeModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's ``Lfm2MoeBlock``."""
    out = {"operator_norm": {"scale": w["operator_norm"]}, "ffn_norm": {"scale": w["ffn_norm"]}}
    if "w_in" in w:
        out["conv"] = {"in_proj": w["w_in"], "taps": w["taps"], "out_proj": w["w_out"]}
    else:
        out["attn"] = {
            "q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"], "out_proj": w["w_o"],
            "q_norm": {"scale": w["q_norm"]}, "k_norm": {"scale": w["k_norm"]},
        }
    if "w_1" in w:
        out["mlp"] = {"gate": w["w_1"], "up": w["w_3"], "down": w["w_2"]}
    else:
        out["moe"] = {
            "router": w["w_router"], "expert_bias": w["b_router"],
            "experts_gate": w["e_gate"], "experts_up": w["e_up"], "experts_down": w["e_down"],
        }
    return out


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout (parameters, gradients or updates),
    rearranged into the program's parameter tree.  Every leaf is float32 on
    both sides, so ``cast`` changes nothing."""
    del sz, cast
    out = {"embedding": tree["emb"], "final_norm": {"scale": tree["final_norm"]}}
    for n, w in enumerate(tree["layers"]):
        out[f"layer_{n}"] = _block(w)
    return out


def draw_batch(key, n, sz):
    """``n`` sequences of uniform random token ids from the vocabulary slice;
    the targets are the same ids, shifted by the loss."""
    return jax.random.randint(key, (n, sz["seq_len"]), 0, sz["vocab_size"], jnp.int32)


def _part_counts(sz):
    """Multiply-adds of one sequence's forward pass, by part: one short
    convolution's two products, one attention layer's projections and core,
    the dense SwiGLU, one expert layer's router and routed experts (the
    *expected* rows: each token's ``k`` choices fall on the held experts with
    probability held / total), the head."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads, kv_heads = sz["num_attention_heads"], sz["num_key_value_heads"]
    size = h // heads
    width, total = sz["moe_intermediate_size"], sz["routed_experts_total"]
    routed_rows = s * sz["num_experts_per_tok"] * sz["experts_held"][1] / total
    return {
        "conv_proj": s * (3 * h * h + h * h),
        "attn_proj": s * (2 * h * heads * size + 2 * h * kv_heads * size),
        # scores and mixing at the published head size, the causal half of the square
        "attn_core": heads * 2 * size * s * s / 2,
        "dense_mlp": s * 3 * h * sz["intermediate_size"],
        "moe_route": s * h * total,
        "moe_experts": routed_rows * 3 * h * width,
        "head": s * h * sz["vocab_size"],
    }


def _layers(sz):
    """``(conv layers, attention layers, dense layers, expert layers)``."""
    types = sz["layer_types"]
    dense = min(sz["num_dense_layers"], len(types))
    return types.count("conv"), types.count("full_attention"), dense, len(types) - dense


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward.  Causal attention at half the
    square, the routed experts at their expected rows, nothing recomputed,
    nothing elementwise (the convolution's gates and taps are not counted)."""
    c, (conv, attn, dense, sparse) = _part_counts(sz), _layers(sz)
    forward = (conv * c["conv_proj"] + attn * (c["attn_proj"] + c["attn_core"])
               + dense * c["dense_mlp"] + sparse * (c["moe_route"] + c["moe_experts"])
               + c["head"])
    return 3.0 * 2.0 * forward


def attention_core_flops_per_sample(sz):
    """Operations of every attention layer's core (scores and mixing, forward
    and backward, 2 per multiply-add, recomputation not counted) in one
    sequence's step: per layer ``3 x 2 x 2 x heads x 64 x s^2 / 2`` at the
    published head size, whatever the kernel pads it to."""
    return 3.0 * 2.0 * _part_counts(sz)["attn_core"] * _layers(sz)[1]


def moe_experts_flops_per_sample(sz):
    """Operations of every expert layer's grouped products in one sequence's
    step at the *expected* routed rows: per layer ``rows x 3 products x 2 x
    hidden x width``, times three for forward and backward."""
    return 3.0 * 2.0 * _part_counts(sz)["moe_experts"] * _layers(sz)[3]
