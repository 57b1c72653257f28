"""Adapter for ``smallthinker-21ba3b``: builds the program's model and loss
through ``bagua_tpu.models.smallthinker_moe``, maps the benchmark's seeded
weights (in the layout of ``reference/smallthinker_moe.py``) onto the program's
parameter tree, draws a batch from the vocabulary slice, and counts operations:
of one sample's training step, and of the three parts whose share of the chip's
peak the benchmark reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "head_dim", "moe_ffn_hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "moe_num_active_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "sliding_window_layout", "sliding_window_size", "rope_layout", "rope_theta",
    "rms_norm_eps", "tie_word_embeddings", "vocab_size",
)


def sizes(config, traffic_input):
    """The file's ``moe_num_primary_experts`` counts the experts *held here*;
    the router keeps the published width
    (``published.moe_num_primary_experts``), and ``deployment.share_held``
    says which of the equal shares this chip is."""
    out = {k: config[k] for k in KEYS}
    for name in ("sliding_window_layout", "rope_layout"):
        out[name] = tuple(out[name])
    held, total = (config["moe_num_primary_experts"],
                   config["published"]["moe_num_primary_experts"])
    if total % held:
        raise ValueError(f"{total} experts do not divide into shares of {held}")
    out["routed_experts_total"] = total
    out["published_layers"] = config["published"]["num_hidden_layers"]
    out["experts_held"] = (config["deployment"]["share_held"] * held, held)
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.smallthinker_moe import HF_KEYS, SmallThinkerConfig

    return SmallThinkerConfig(
        **{k: sz[k] for k in HF_KEYS if k in sz},
        moe_num_primary_experts=sz["routed_experts_total"], experts_held=sz["experts_held"],
        compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.smallthinker_moe import SmallThinkerModel, smallthinker_loss_fn

    return smallthinker_loss_fn(SmallThinkerModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's
    ``SmallThinkerBlock``."""
    return {
        "input_norm": {"scale": w["norm_in"]}, "post_attention_norm": {"scale": w["norm_post"]},
        "router": w["w_router"],
        "attn": {"q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"], "out_proj": w["w_o"]},
        "experts_gate": w["e_gate"], "experts_up": w["e_up"], "experts_down": w["e_down"],
    }


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout (parameters, gradients or updates),
    rearranged into the program's parameter tree.  Every leaf is float32 on
    both sides, so ``cast`` changes nothing."""
    del sz, cast
    out = {"embedding": tree["emb"], "final_norm": {"scale": tree["final_norm"]},
           "lm_head": tree["w_head"]}
    for n, w in enumerate(tree["layers"]):
        out[f"layer_{n}"] = _block(w)
    return out


def draw_batch(key, n, sz):
    """``n`` sequences of uniform random token ids from the vocabulary slice;
    the targets are the same ids, shifted by the loss."""
    return jax.random.randint(key, (n, sz["seq_len"]), 0, sz["vocab_size"], jnp.int32)


def attended_pairs(s: int, window=None) -> int:
    """``(i, j)`` with ``0 <= j <= i < s`` and, with a window, ``i - j <
    window``: the scores a layer's mask leaves open, counted whole."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _part_counts(sz):
    """Multiply-adds of one sequence's forward pass, by part of one layer: the
    four projections, the core under each mask (scores and mixing over the
    pairs the mask leaves open, none recomputed), the router, the held
    experts' three products at their *expected* rows (each token's ``k``
    choices fall on the held experts with probability held / total), and the
    head."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads, kv_heads, size = sz["num_attention_heads"], sz["num_key_value_heads"], sz["head_dim"]
    total = sz["routed_experts_total"]
    routed_rows = s * sz["moe_num_active_primary_experts"] * sz["experts_held"][1] / total
    return {
        "attn_proj": s * (2 * h * heads * size + 2 * h * kv_heads * size),
        "attn_core": heads * 2 * size * attended_pairs(s),
        "attn_window_core": heads * 2 * size * attended_pairs(s, sz["sliding_window_size"]),
        "moe_route": s * h * total,
        "moe_experts": routed_rows * 3 * h * sz["moe_ffn_hidden_size"],
        "head": s * h * sz["vocab_size"],
    }


def _layers(sz):
    """``(global layers, windowed layers)``."""
    windowed = sum(sz["sliding_window_layout"])
    return len(sz["sliding_window_layout"]) - windowed, windowed


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward.  Attention over the pairs its mask
    leaves open, the experts at their expected rows, nothing recomputed,
    nothing elementwise."""
    c, (full, windowed) = _part_counts(sz), _layers(sz)
    every = c["attn_proj"] + c["moe_route"] + c["moe_experts"]
    forward = ((full + windowed) * every + full * c["attn_core"]
               + windowed * c["attn_window_core"] + c["head"])
    return 3.0 * 2.0 * forward


def attention_core_flops_per_sample(sz):
    """Operations of every *global* layer's core (scores and mixing, forward
    and backward, 2 per multiply-add, recomputation not counted) in one
    sequence's step: per layer ``3 x 2 x 2 x heads x 128 x s (s + 1) / 2``."""
    return 3.0 * 2.0 * _part_counts(sz)["attn_core"] * _layers(sz)[0]


def window_attention_core_flops_per_sample(sz):
    """The same of every *windowed* layer's core, over the pairs inside the
    window alone: at 8,192 positions and 4,096 keys 25,167,872 of the causal
    33,558,528."""
    return 3.0 * 2.0 * _part_counts(sz)["attn_window_core"] * _layers(sz)[1]


def moe_experts_flops_per_sample(sz):
    """Operations of every layer's grouped products in one sequence's step at
    the *expected* routed rows: per layer ``rows x 3 products x 2 x hidden x
    width``, times three for forward and backward."""
    return 3.0 * 2.0 * _part_counts(sz)["moe_experts"] * sum(_layers(sz))
