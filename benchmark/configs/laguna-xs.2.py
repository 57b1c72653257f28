"""Adapter for ``laguna-xs.2``: builds the program's model and loss through
``bagua_tpu.models.laguna``, maps the benchmark's seeded weights (in the layout
of ``reference/laguna.py``) onto the program's parameter tree, draws a batch
from the vocabulary slice, and counts operations: of one sample's training
step, and of the three parts whose share of the chip's peak the benchmark
reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_key_value_heads", "head_dim",
    "rms_norm_eps", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "tie_word_embeddings", "gating", "sliding_window",
    "layer_types", "mlp_layer_types", "moe_routed_scaling_factor", "num_attention_heads_per_layer",
    "moe_apply_router_weight_on_input", "attention_bias", "vocab_size",
)
SLIDING = "sliding_attention"


def sizes(config, traffic_input):
    """The file's ``num_experts`` counts the experts *held here*; the router
    keeps the published width (``published.num_experts``), and
    ``deployment.share_held`` says which of the equal shares this chip is."""
    out = {k: config[k] for k in KEYS}
    for name in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        out[name] = tuple(out[name])
    # the entries by layer type; the group's own scalars say nothing of a layer
    out["rope_parameters"] = {
        kind: dict(entry) for kind, entry in config["rope_parameters"].items()
        if isinstance(entry, dict)}
    held, total = config["num_experts"], config["published"]["num_experts"]
    if total % held:
        raise ValueError(f"{total} experts do not divide into shares of {held}")
    out["routed_experts_total"] = total
    out["published_layers"] = config["published"]["num_hidden_layers"]
    out["experts_held"] = (config["deployment"]["share_held"] * held, held)
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    if "init_std" in config:  # the toy sizes' alone
        out["init_std"] = config["init_std"]
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.laguna import HF_KEYS, LagunaConfig

    return LagunaConfig.from_hf(
        {k: sz[k] for k in HF_KEYS if k in sz}, num_experts=sz["routed_experts_total"],
        experts_held=sz["experts_held"], compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.laguna import LagunaModel, laguna_loss_fn

    return laguna_loss_fn(LagunaModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's ``LagunaBlock``."""
    out = {
        "input_norm": {"scale": w["norm_in"]}, "post_attention_norm": {"scale": w["norm_post"]},
        "attn": {"q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"],
                 "gate_proj": w["w_g"], "out_proj": w["w_o"]},
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


def expected_routed_rows(sz) -> float:
    """Rows the held experts expect of one sequence: each token's ``k``
    choices fall on them with probability held / total."""
    return (sz["seq_len"] * sz["num_experts_per_tok"] * sz["experts_held"][1]
            / sz["routed_experts_total"])


def _layer_counts(sz, n):
    """Multiply-adds of one sequence's forward pass in layer ``n``, by part:
    the four projections at the layer's own head count, the gate's product,
    the core over the pairs its mask leaves open (none recomputed), and the
    layer's MLP: the dense one, or the router, the shared expert and the held
    experts' three products at their *expected* rows."""
    s, h, size = sz["seq_len"], sz["hidden_size"], sz["head_dim"]
    heads, kv_heads = sz["num_attention_heads_per_layer"][n], sz["num_key_value_heads"]
    windowed = sz["layer_types"][n] == SLIDING
    out = {
        "attn_proj": s * (2 * h * heads * size + 2 * h * kv_heads * size),
        "attn_gate": s * h * heads if sz["gating"] else 0,
        "attn_window_core" if windowed else "attn_core": heads * 2 * size * attended_pairs(
            s, sz["sliding_window"] if windowed else None),
    }
    if sz["mlp_layer_types"][n] == "dense":
        out["dense_mlp"] = s * 3 * h * sz["intermediate_size"]
    else:
        out["moe_route"] = s * h * sz["routed_experts_total"]
        out["moe_shared"] = s * 3 * h * sz["shared_expert_intermediate_size"]
        out["moe_experts"] = expected_routed_rows(sz) * 3 * h * sz["moe_intermediate_size"]
    return out


def part_counts(sz) -> dict:
    """Multiply-adds of one sequence's forward pass by part, over all layers
    and the head."""
    total = {"head": sz["seq_len"] * sz["hidden_size"] * sz["vocab_size"]}
    for n in range(sz["num_hidden_layers"]):
        for part, count in _layer_counts(sz, n).items():
            total[part] = total.get(part, 0) + count
    return total


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward.  Attention over the pairs its mask
    leaves open, the experts at their expected rows, nothing recomputed,
    nothing elementwise."""
    return 3.0 * 2.0 * sum(part_counts(sz).values())


def attention_core_flops_per_sample(sz):
    """Operations of every *global* layer's core (scores and mixing, forward
    and backward, 2 per multiply-add, recomputation not counted) in one
    sequence's step: per layer ``3 x 2 x 2 x 48 x 128 x s (s + 1) / 2``."""
    return 3.0 * 2.0 * part_counts(sz).get("attn_core", 0)


def window_attention_core_flops_per_sample(sz):
    """The same of every *windowed* layer's core, over the pairs inside the
    window alone: at 8,192 positions and 512 keys 4,063,488 of the causal
    33,558,528, at 64 query heads."""
    return 3.0 * 2.0 * part_counts(sz).get("attn_window_core", 0)


def moe_experts_flops_per_sample(sz):
    """Operations of every expert layer's grouped products in one sequence's
    step at the *expected* routed rows: per layer ``rows x 3 products x 2 x
    hidden x width``, times three for forward and backward."""
    return 3.0 * 2.0 * part_counts(sz).get("moe_experts", 0)
