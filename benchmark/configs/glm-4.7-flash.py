"""Adapter for ``glm-4.7-flash``: builds the program's model and loss through
``bagua_tpu.models.glm_moe``, maps the benchmark's seeded weights (in the
layout of ``reference/glm_moe.py``) onto the program's parameter tree, draws
a batch from the vocabulary slice, and counts operations: of one sample's
training step, and of the two parts whose share of the chip's peak the
benchmark reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob", "rope_theta",
    "rms_norm_eps", "vocab_size", "num_nextn_predict_layers", "mtp_loss_weight",
)


def sizes(config, traffic_input):
    """The file's ``n_routed_experts`` counts the experts *held here*; the
    router keeps the published width (``published.n_routed_experts``), and
    ``deployment.share_held`` says which of the equal shares this chip is."""
    out = {k: config[k] for k in KEYS}
    held, total = config["n_routed_experts"], config["published"]["n_routed_experts"]
    if total % held:
        raise ValueError(f"{total} routed experts do not divide into shares of {held}")
    out["routed_experts_total"] = total
    out["experts_held"] = (config["deployment"]["share_held"] * held, held)
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.glm_moe import HF_KEYS, GlmMoeConfig

    return GlmMoeConfig(
        **{k: sz[k] for k in HF_KEYS if k in sz}, n_routed_experts=sz["routed_experts_total"],
        experts_held=sz["experts_held"], mtp_loss_weight=sz["mtp_loss_weight"],
        compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.glm_moe import GlmMoeModel, glm_moe_loss_fn

    return glm_moe_loss_fn(GlmMoeModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's ``GlmMoeBlock``."""
    out = {
        "attn_norm": {"scale": w["attn_norm"]},
        "attn": {
            "q_down": w["w_dq"], "q_norm": {"scale": w["q_norm"]}, "q_up": w["w_uq"],
            "kv_down": w["w_dkv"], "kv_norm": {"scale": w["kv_norm"]}, "kv_up": w["w_ukv"],
            "out": w["w_o"],
        },
        "mlp_norm": {"scale": w["mlp_norm"]},
    }
    if "w_gate" in w:
        out["mlp"] = {"gate": w["w_gate"], "up": w["w_up"], "down": w["w_down"]}
    else:
        out["moe"] = {
            "router": w["w_router"], "correction_bias": w["b_router"],
            "experts_gate": w["e_gate"], "experts_up": w["e_up"], "experts_down": w["e_down"],
            "shared": {"gate": w["s_gate"], "up": w["s_up"], "down": w["s_down"]},
        }
    return out


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout (parameters, gradients or updates),
    rearranged into the program's parameter tree.  Every leaf is float32 on
    both sides, so ``cast`` changes nothing."""
    del sz, cast
    out = {
        "embedding": tree["emb"], "lm_head": tree["head"],
        "final_norm": {"scale": tree["final_norm"]},
    }
    for n, w in enumerate(tree["layers"]):
        out[f"layer_{n}"] = _block(w)
    if "mtp" in tree:
        m = tree["mtp"]
        out.update(
            mtp_embed_norm={"scale": m["emb_norm"]}, mtp_hidden_norm={"scale": m["hidden_norm"]},
            mtp_proj=m["w_eh"], mtp_block=_block(m["layer"]),
            mtp_final_norm={"scale": m["final_norm"]})
    return out


def draw_batch(key, n, sz):
    """``n`` sequences of uniform random token ids from the vocabulary slice;
    the targets are the same ids, shifted by the loss."""
    return jax.random.randint(key, (n, sz["seq_len"]), 0, sz["vocab_size"], jnp.int32)


def _layer_counts(sz):
    """Multiply-adds of one sequence's forward pass, by part: the latent
    projections and the attention core of one layer, the dense layer's
    SwiGLU, one expert layer's router, shared expert and routed experts (the
    *expected* rows: each token's ``k`` choices fall on the held experts with
    probability held / total), the head."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads, nope, rope, dv = (sz["num_attention_heads"], sz["qk_nope_head_dim"],
                             sz["qk_rope_head_dim"], sz["v_head_dim"])
    width, total = sz["moe_intermediate_size"], sz["routed_experts_total"]
    routed_rows = s * sz["num_experts_per_tok"] * sz["experts_held"][1] / total
    return {
        "attn_proj": s * (h * sz["q_lora_rank"] + sz["q_lora_rank"] * heads * (nope + rope)
                          + h * (sz["kv_lora_rank"] + rope)
                          + sz["kv_lora_rank"] * heads * (nope + dv) + heads * dv * h),
        # scores and mixing, the causal half of the square
        "attn_core": heads * (nope + rope + dv) * s * s / 2,
        "dense_mlp": s * 3 * h * sz["intermediate_size"],
        "moe_route": s * h * total,
        "moe_shared": s * 3 * h * width * sz["n_shared_experts"],
        "moe_experts": routed_rows * 3 * h * width,
        "head": s * h * sz["vocab_size"],
    }


def _layers(sz):
    dense = min(sz["first_k_dense_replace"], sz["num_hidden_layers"])
    return dense, sz["num_hidden_layers"] - dense


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward.  Causal attention at half the
    square, the routed experts at their expected rows, nothing recomputed,
    nothing elementwise; with the prediction module, its projection, expert
    layer and second use of the head."""
    c, (dense, sparse) = _layer_counts(sz), _layers(sz)
    expert_layer = c["attn_proj"] + c["attn_core"] + c["moe_route"] + c["moe_shared"] + c["moe_experts"]
    forward = (dense * (c["attn_proj"] + c["attn_core"] + c["dense_mlp"])
               + sparse * expert_layer + c["head"])
    if sz["num_nextn_predict_layers"]:
        forward += sz["seq_len"] * 2 * sz["hidden_size"] ** 2 + expert_layer + c["head"]
    return 3.0 * 2.0 * forward


def attention_core_flops_per_sample(sz):
    """Operations of every layer's attention core (scores and mixing, forward
    and backward, 2 per multiply-add, recomputation not counted) in one
    sequence's step: per layer ``3 x 2 x 2 x heads x 256 x s^2 / 2`` at the
    published head sizes."""
    return 3.0 * 2.0 * _layer_counts(sz)["attn_core"] * sz["num_hidden_layers"]


def moe_experts_flops_per_sample(sz):
    """Operations of every expert layer's grouped products in one sequence's
    step at the *expected* routed rows: per layer ``rows x 3 products x 2 x
    hidden x width``, times three for forward and backward."""
    return 3.0 * 2.0 * _layer_counts(sz)["moe_experts"] * _layers(sz)[1]
