"""Adapter for ``nemotron-3-super``: builds the program's model and loss through
``bagua_tpu.models.nemotron_h``, maps the benchmark's seeded weights (in the
layout of ``reference/nemotron_h.py``) onto the program's parameter tree, draws
a batch from the vocabulary slice, and counts operations and bytes: of one
sample's training step, and of the parts whose share of the chip's peaks the
benchmark reports."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree: the output matrix
HEAD_LEAF = "['lm_head']"

#: keys of the configuration's file that size the model, as published
KEYS = (
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern", "intermediate_size",
    "mamba_head_dim", "ssm_state_size", "conv_kernel", "chunk_size", "use_conv_bias",
    "mamba_hidden_act", "time_step_min", "time_step_max", "head_dim", "num_experts_per_tok",
    "moe_intermediate_size", "moe_latent_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group",
    "mlp_hidden_act", "layer_norm_epsilon", "tie_word_embeddings", "num_nextn_predict_layers",
    "vocab_size",
)
#: the file's count of what is *held here*, and the share that says which
HELD = (("experts_held", "n_routed_experts", "share_held"),
        ("mamba_heads_held", "mamba_num_heads", "mixer_share_held"),
        ("attention_heads_held", "num_attention_heads", "mixer_share_held"))


def sizes(config, traffic_input):
    """The file's ``n_routed_experts``, ``mamba_num_heads`` (with ``n_groups``)
    and ``num_attention_heads`` (with ``num_key_value_heads``) count what is
    *held here*; the router, the groups' size and the queries a key-value head
    keep the published counts (``published``), and ``deployment`` says which of
    the equal shares this chip is."""
    out = {k: config[k] for k in KEYS}
    published, deployment = config["published"], config["deployment"]
    for name, key, share in HELD:
        held, total = config[key], published[key]
        if total % held:
            raise ValueError(f"{total} of {key} do not divide into shares of {held}")
        out[name] = (deployment[share] * held, held)
    out["routed_experts_total"] = published["n_routed_experts"]
    out["mamba_heads_total"] = published["mamba_num_heads"]
    out["n_groups_total"] = published["n_groups"]
    out["attention_heads_total"] = published["num_attention_heads"]
    out["key_value_heads_total"] = published["num_key_value_heads"]
    out["published_layers"] = published["num_hidden_layers"]
    if (out["mamba_heads_total"] * config["n_groups"]
            != config["mamba_num_heads"] * out["n_groups_total"]):
        raise ValueError("the held heads are no whole groups of the published size")
    if len(out["hybrid_override_pattern"]) != out["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name num_hidden_layers blocks")
    if "init_std" in config:  # the toy sizes' alone
        out["init_std"] = config["init_std"]
    out["seq_len"] = traffic_input["seq_len"]
    if out["seq_len"] > config["max_position_embeddings"]:
        raise ValueError(f"seq_len {out['seq_len']} exceeds max_position_embeddings")
    return out


def model_config(sz, compute_dtype=jnp.bfloat16):
    from bagua_tpu.models.nemotron_h import HF_KEYS, NemotronHConfig

    return NemotronHConfig(
        **{k: sz[k] for k in HF_KEYS if k in sz},
        n_routed_experts=sz["routed_experts_total"], mamba_num_heads=sz["mamba_heads_total"],
        n_groups=sz["n_groups_total"], num_attention_heads=sz["attention_heads_total"],
        num_key_value_heads=sz["key_value_heads_total"], experts_held=sz["experts_held"],
        mamba_heads_held=sz["mamba_heads_held"], attention_heads_held=sz["attention_heads_held"],
        compute_dtype=compute_dtype)


def build_loss(sz):
    from bagua_tpu.models.nemotron_h import NemotronHModel, nemotron_h_loss_fn

    return nemotron_h_loss_fn(NemotronHModel(model_config(sz)))


def as_stored(ref_params):
    """The program stores every parameter in float32: nothing to round."""
    return ref_params


def _block(w):
    """One block of the reference's tree as the program's ``NemotronHBlock``:
    the reference's leaves tell the kind."""
    norm = {"norm": {"scale": w["norm"]}}
    if "w_in" in w:
        return {**norm, "mixer": {
            "in_proj": w["w_in"], "conv_taps": w["conv_w"], "conv_bias": w["conv_b"],
            "dt_bias": w["dt_bias"], "A_log": w["a_log"], "D": w["d_skip"],
            "norm_scale": w["gate_norm"], "out_proj": w["w_out"]}}
    if "w_q" in w:
        return {**norm, "attn": {"q_proj": w["w_q"], "k_proj": w["w_k"], "v_proj": w["w_v"],
                                 "out_proj": w["w_o"]}}
    if "w_router" in w:
        return {**norm, "moe": {
            "router": w["w_router"], "correction_bias": w["b_router"],
            "latent_in": w["w_lat_in"], "latent_out": w["w_lat_out"],
            "experts_up": w["e_up"], "experts_down": w["e_down"],
            "shared": {"up": w["s_up"], "down": w["s_down"]}}}
    return {**norm, "mlp": {"up": w["m_up"], "down": w["m_down"]}}


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


def _mixer_widths(sz):
    heads = sz["mamba_heads_held"][1]
    groups = heads * sz["n_groups_total"] // sz["mamba_heads_total"]
    inner = heads * sz["mamba_head_dim"]
    return heads, groups, inner, inner + 2 * groups * sz["ssm_state_size"]


def _part_counts(sz):
    """Multiply-adds of one sequence's forward pass, by part of one block of
    its kind.  The scan as its chunked form has it, three products: inside a
    chunk the scores ``C B^T`` a group and their product with ``dt x`` a head,
    over the pairs at or under the diagonal; each chunk's own end state; the
    carried state read through ``C``.  The held experts' two products at their
    *expected* rows.  Attention over the pairs the causal mask leaves open."""
    s, h = sz["seq_len"], sz["hidden_size"]
    heads, groups, inner, channels = _mixer_widths(sz)
    size, state, chunk = sz["mamba_head_dim"], sz["ssm_state_size"], min(sz["chunk_size"], s)
    q_heads, head = sz["attention_heads_held"][1], sz["head_dim"]
    kv_heads = max(1, q_heads * sz["key_value_heads_total"] // sz["attention_heads_total"])
    return {
        "ssm_proj": s * h * (inner + channels + heads) + s * inner * h,
        "ssm_core": (s * (chunk + 1) / 2 * (groups * state + heads * size)
                     + 2 * s * heads * size * state),
        "attn_proj": s * h * head * (2 * q_heads + 2 * kv_heads),
        "attn_core": q_heads * 2 * head * s * (s + 1) // 2,
        "moe_route": s * h * sz["routed_experts_total"],
        "moe_latent": 2 * s * h * sz["moe_latent_size"],
        "moe_experts": (expected_routed_rows(sz) * 2 * sz["moe_latent_size"]
                        * sz["moe_intermediate_size"]),
        "moe_shared": 2 * s * h * sz["moe_shared_expert_intermediate_size"],
        "dense_mlp": 2 * s * h * sz["intermediate_size"],
        "head": s * h * sz["vocab_size"],
    }


#: the parts of a block of each kind
PARTS = {"M": ("ssm_proj", "ssm_core"), "*": ("attn_proj", "attn_core"),
         "E": ("moe_route", "moe_latent", "moe_experts", "moe_shared"), "-": ("dense_mlp",)}


def _blocks(sz, kind: str) -> int:
    return sz["hybrid_override_pattern"].count(kind)


def train_flops_per_sample(sz):
    """Floating-point operations one sequence needs in a training step: the
    forward pass's matrix multiplications at two operations per multiply-add,
    times three for forward and backward.  The experts at their expected rows,
    the scan's three chunk products, attention over the open pairs; nothing
    recomputed, nothing elementwise (the convolution, the decays, the gate and
    the norms are no products)."""
    c = _part_counts(sz)
    forward = c["head"] + sum(
        _blocks(sz, kind) * sum(c[part] for part in parts) for kind, parts in PARTS.items())
    return 3.0 * 2.0 * forward


def ssm_core_flops_per_sample(sz):
    """Operations of every mixer's scan in one sequence's step: the three
    chunk products, forward and backward, what is built again not counted."""
    return 3.0 * 2.0 * _part_counts(sz)["ssm_core"] * _blocks(sz, "M")


def ssm_core_bytes_per_sample(sz):
    """Bytes no implementation of a mixer's core avoids, in one sequence's
    step: ``x``, ``B``, ``C`` and ``z`` (two bytes a number) and ``dt`` (four)
    read and ``y`` written once forward, and as much again for their
    cotangents backward."""
    heads, groups, inner, channels = _mixer_widths(sz)
    forward = sz["seq_len"] * (2 * (channels + inner) + 4 * heads + 2 * inner)
    return 2.0 * forward * _blocks(sz, "M")


def moe_experts_flops_per_sample(sz):
    """Operations of every expert layer's grouped products in one sequence's
    step at the *expected* routed rows: per layer ``rows x 2 products x 2 x
    latent x width``, times three for forward and backward."""
    return 3.0 * 2.0 * _part_counts(sz)["moe_experts"] * _blocks(sz, "E")


def attention_core_flops_per_sample(sz):
    """Operations of every attention layer's core (scores and mixing, forward
    and backward, 2 per multiply-add, recomputation not counted) in one
    sequence's step: per layer ``3 x 2 x 2 x heads x 128 x s (s + 1) / 2``."""
    return 3.0 * 2.0 * _part_counts(sz)["attn_core"] * _blocks(sz, "*")
