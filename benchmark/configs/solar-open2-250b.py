"""Adapter for ``solar-open2-250b``: builds the program's model and loss through
``bagua_tpu.models.solar_open2``, maps the benchmark's seeded weights (in the
layout of ``reference/solar_open2.py``) onto the program's parameter tree, draws
a batch from the vocabulary slice, and counts operations and bytes: of one
sample's training step, and of the parts whose share of the chip's peaks the
benchmark reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "num_hidden_layers", "head_dim", "intermediate_size", "moe_intermediate_size",
    "rms_norm_eps", "tie_word_embeddings", "first_k_dense_replace", "use_rope", "use_gqa_gate",
    "kda_use_full_proj", "kda_allow_neg_eigval", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor", "num_experts_per_tok", "chunk_size", "vocab_size",
)


def sizes(config, traffic_input):
    """The file's ``n_routed_experts``, ``num_attention_heads`` (with
    ``num_key_value_heads``) and ``linear_attn_config.num_heads`` count what is
    *held here*; the router and the queries a key-value head keep the published
    counts (``published``), and ``deployment`` says which of the equal shares
    this chip is."""
    out = {k: config[k] for k in KEYS}
    published, deployment = config["published"], config["deployment"]
    linear, linear_published = config["linear_attn_config"], published["linear_attn_config"]
    held, total = config["num_attention_heads"], published["num_attention_heads"]
    if (linear["num_heads"], linear_published["num_heads"]) != (held, total):
        raise ValueError("one range of heads is held of both mixers: the two counts differ")
    if linear["num_kv_heads"] is not None:
        raise ValueError("built for KDA keys and values on the query's heads")
    for count, whole, what in ((held, total, "heads"),
                               (config["n_routed_experts"], published["n_routed_experts"], "experts")):
        if whole % count:
            raise ValueError(f"{whole} {what} do not divide into shares of {count}")
    if total * config["num_key_value_heads"] != held * published["num_key_value_heads"]:
        raise ValueError("the held key-value heads are not the held query heads'")
    out["heads_held"] = (deployment["mixer_share_held"] * held, held)
    out["experts_held"] = (deployment["share_held"] * config["n_routed_experts"],
                           config["n_routed_experts"])
    out["attention_heads_total"] = total
    out["key_value_heads_total"] = published["num_key_value_heads"]
    out["routed_experts_total"] = published["n_routed_experts"]
    out["published_layers"] = published["num_hidden_layers"]
    out["gqa_layers"] = tuple(config["gqa_layers"])
    out["kda_head_dim"] = linear["head_dim"]
    out["short_conv_kernel_size"] = linear["short_conv_kernel_size"]
    if any(not 0 <= n < out["num_hidden_layers"] for n in out["gqa_layers"]):
        raise ValueError("gqa_layers names a layer past num_hidden_layers")
    for key in ("init_std", "dt_range"):  # the toy sizes' alone
        if key in config:
            out[key] = tuple(config[key]) if key == "dt_range" else config[key]
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.solar_open2 import HF_KEYS, LinearAttnConfig, SolarOpen2Config

    return SolarOpen2Config.from_hf(
        {k: sz[k] for k in HF_KEYS if k in sz}, gqa_layers=sz["gqa_layers"],
        num_attention_heads=sz["attention_heads_total"],
        num_key_value_heads=sz["key_value_heads_total"],
        n_routed_experts=sz["routed_experts_total"],
        linear_attn_config=LinearAttnConfig(
            sz["short_conv_kernel_size"], sz["kda_head_dim"], sz["attention_heads_total"], None),
        chunk_size=sz["chunk_size"], heads_held=sz["heads_held"], experts_held=sz["experts_held"],
        compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.solar_open2 import SolarOpen2Model, solar_open2_loss_fn

    return solar_open2_loss_fn(SolarOpen2Model(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One layer of the reference's tree as the program's ``SolarOpen2Block``:
    the reference's leaves tell the kind of its mixer."""
    out = {
        "input_norm": {"scale": w["norm_in"]}, "post_mixer_norm": {"scale": w["norm_post"]},
        "moe": {
            "router": w["w_router"], "correction_bias": w["b_router"],
            "experts_gate": w["e_gate"], "experts_up": w["e_up"], "experts_down": w["e_down"],
            "shared": {"gate": w["s_gate"], "up": w["s_up"], "down": w["s_down"]}},
    }
    if "w_f1" in w:
        out["kda"] = {
            "q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"],
            "q_conv": w["conv_q"], "k_conv": w["conv_k"], "v_conv": w["conv_v"],
            "f_a_proj": w["w_f1"], "f_b_proj": w["w_f2"], "dt_bias": w["dt_bias"],
            "A_log": w["a_log"], "b_proj": w["w_b"], "g_a_proj": w["w_g1"],
            "g_b_proj": w["w_g2"], "g_bias": w["b_g"], "o_norm": w["o_norm"], "o_proj": w["w_o"]}
    else:
        out["attn"] = {"q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"],
                       "gate_proj": w["w_g"], "out_proj": w["w_o"]}
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


def expected_routed_rows(sz) -> float:
    """The rows a layer's held experts get when every choice is as likely to
    fall on one expert as on another: ``tokens x k x held / total``."""
    return (sz["seq_len"] * sz["num_experts_per_tok"] * sz["experts_held"][1]
            / sz["routed_experts_total"])


def _key_value_heads_held(sz) -> int:
    return max(1, sz["heads_held"][1] * sz["key_value_heads_total"] // sz["attention_heads_total"])


def _layer_counts(sz, n):
    """Multiply-adds of one sequence's forward pass in layer ``n``, by part.
    The GQA mixer: four projections and the gate's product, the core over the
    pairs the causal mask leaves open.  The KDA mixer: the four wide products
    and the four narrow ones (the decay's and the gate's pairs through the head
    size, ``beta``'s), and the delta rule *as its chunked form has it* at the
    configuration's ``chunk_size`` ``C``, a head and position: the two decayed
    scores ``k k^T`` and ``q k^T`` over the pairs at or under a chunk's diagonal
    (``2 d (C + 1) / 2``), the triangular system's inverse applied to ``[v | k]``
    (``2 d (C + 1) / 2``), the scores times the corrected values (``d (C + 1) /
    2``), and three products with the carried state, ``d x d`` each (what it
    gives under ``k``, what ``q`` reads of it, the chunk's own end state).  The
    experts: the router, the shared expert and the held experts' three products
    at their *expected* rows."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads = sz["heads_held"][1]
    if n in sz["gqa_layers"]:
        size, kv_heads = sz["head_dim"], _key_value_heads_held(sz)
        out = {"attn_proj": s * h * size * (2 * heads + 2 * kv_heads),
               "attn_gate": s * h * heads * size if sz["use_gqa_gate"] else 0,
               "attn_core": heads * 2 * size * (s * (s + 1) // 2)}
    else:
        size, chunk = sz["kda_head_dim"], min(sz["chunk_size"], s)
        out = {"kda_proj": s * (4 * h * heads * size + 2 * (h * size + size * heads * size)
                                + h * heads),
               "kda_core": s * heads * (5 * size * (chunk + 1) / 2 + 3 * size * size)}
    width = sz["moe_intermediate_size"]
    out["moe_route"] = s * h * sz["routed_experts_total"]
    out["moe_shared"] = s * 3 * h * width * sz["n_shared_experts"]
    out["moe_experts"] = expected_routed_rows(sz) * 3 * h * width
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
    times three for forward and backward.  Attention over the open pairs, the
    delta rule's chunk products, the experts at their expected rows; nothing
    recomputed (``kda_core`` is built again backward, and counted once),
    nothing elementwise (the convolutions, the decays, the gates and the norms
    are no products)."""
    return 3.0 * 2.0 * sum(part_counts(sz).values())


def kda_core_flops_per_sample(sz):
    """Operations of every KDA mixer's delta rule in one sequence's step: the
    chunked form's products at the configuration's ``chunk_size``, forward and
    backward, what is built again not counted; a function of the sizes alone."""
    return 3.0 * 2.0 * part_counts(sz).get("kda_core", 0)


def kda_core_bytes_per_sample(sz):
    """Bytes no implementation of a KDA mixer's core avoids, in one sequence's
    step: ``q``, ``k`` and ``v`` (two bytes a number), ``g`` (four, a channel)
    and ``beta`` (four, a head) read and ``o`` written (two) once forward, and
    as much again for their cotangents backward."""
    heads, size = sz["heads_held"][1], sz["kda_head_dim"]
    forward = sz["seq_len"] * heads * (size * (3 * 2 + 4 + 2) + 4)
    layers = sz["num_hidden_layers"] - len(sz["gqa_layers"])
    return 2.0 * forward * layers


def attention_core_flops_per_sample(sz):
    """Operations of every GQA layer's core (scores and mixing, forward and
    backward, 2 per multiply-add, recomputation not counted) in one sequence's
    step: per layer ``3 x 2 x 2 x heads x 128 x s (s + 1) / 2``."""
    return 3.0 * 2.0 * part_counts(sz).get("attn_core", 0)


def moe_experts_flops_per_sample(sz):
    """Operations of every expert layer's grouped products in one sequence's
    step at the *expected* routed rows: per layer ``rows x 3 products x 2 x
    hidden x width``, times three for forward and backward."""
    return 3.0 * 2.0 * part_counts(sz).get("moe_experts", 0)
