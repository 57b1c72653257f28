"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a causal decoder with
multi-head *latent* attention, a leading dense layer, expert layers with a
sigmoid top-k router over all routed experts, a shared expert, and a
multi-token-prediction module.  Built from the keys of the published
``config.json`` (:meth:`GlmMoeConfig.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`, the one lookup
of the three expert models, which also says what an id outside the vocabulary
does; the prediction module's lookup of the next token is the same function).
Per layer, on the residual stream ``x`` (RMSNorm with a learned scale, no bias
anywhere):

* attention: ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` split per head into a
  part without position (``qk_nope_head_dim``) and a rotary part
  (``qk_rope_head_dim``); ``[c_kv | k_rope] = x W_dkv``, ``[k_nope | v] =
  RMSNorm(c_kv) W_ukv``; RoPE on ``q_rope`` and on the one ``k_rope`` all heads
  share; ``softmax(q k^T / sqrt(nope + rope) + causal) v``; ``W_o``.
* dense layer (the first ``first_k_dense_replace``): SwiGLU of
  ``intermediate_size``.
* expert layer: :func:`~bagua_tpu.parallel.moe.dropless.sigmoid_topk_route`
  over all ``n_routed_experts``, the part of the chosen experts this chip
  *holds* (``experts_held``, :func:`~bagua_tpu.parallel.moe.dropless.dropless_experts`)
  plus the shared expert, each a SwiGLU of ``moe_intermediate_size``.
* multi-token prediction (``num_nextn_predict_layers``, DeepSeek-V3 section
  2.2): ``h' = W_eh [RMSNorm(Emb(t_{i+1})) | RMSNorm(x_i)]``, one expert layer,
  a final norm of its own, the model's embedding and output matrices,
  cross-entropy against ``t_{i+2}`` added with weight ``mtp_loss_weight``.

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the rotation, the router, the
logits and the loss are float32.  Each part of the forward pass sits under a
``bagua_model/part=...`` scope, so a device trace tells the parts apart.

The parts shared with the other decoder models are ``models/decoder.py``'s
(``RMSNorm``, ``Kernels``, ``matmul``, ``product``, ``HEADS_MAJOR``, ``SwiGLU``).

How the attention's operands are written (:func:`latent_qk`; ``PERF.md``
section 6, PR 32).  The attention kernels read ``q``, ``k``, ``v`` as
``(batch, heads, positions, head size)``, so the products contract onto that
layout and ``W_o`` contracts the kernels' result over ``(heads, head size)``:
no array with the positions in it is transposed, sliced by stride or
concatenated from narrow pieces.  ``q k^T`` is a sum over a head's ``nope +
rope`` score columns, the same whatever their order as long as ``q`` and
``k`` share it, and the published rotary embedding (the interleaved
convention: stored columns ``2 i`` and ``2 i + 1`` are a pair) only needs
each pair's two numbers side by side.  So the layer takes a head's score
columns in the order ``[nope a | first of every pair | nope b | second of
every pair]``, half the plain columns in ``a`` and half in ``b``: the two of
a pair lie half a head apart, and the rotation is ``first * cos - second *
sin`` and ``second * cos + first * sin`` between the two halves of a head,
with cos 1 and sin 0 on the plain columns and the scores' scale in both
tables: one pass in float32 over the product's result, one rounding.  The
order is made by slicing and reshaping the *weights* (``W_uq``'s columns,
``W_dkv``'s rotary columns, ``W_ukv``'s ``k_nope`` and ``v`` columns apart);
the stored parameters keep their shapes and column order, and their
gradients arrive there by the same slices transposed.  ``k``'s product
writes all ``nope + rope`` columns through zero columns of the weight where
the rotary ones go, and the one rotary key all heads share is added to its
result: each element is the dot product it was, plus an exact zero.
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.models.decoder import HEADS_MAJOR, Kernels, RMSNorm, SwiGLU, matmul, product
from bagua_tpu.models.embedding import embed
from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "routed_scaling_factor", "norm_topk_prob", "rope_theta", "rms_norm_eps",
    "num_nextn_predict_layers",
)


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1
    #: weight of the multi-token-prediction loss; not in ``config.json``
    mtp_loss_weight: float = 0.3
    #: ``(first, count)`` of the routed experts whose kernels live here;
    #: None: all of them
    experts_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.n_routed_experts} routed experts")
        if self.qk_rope_head_dim % 2 or self.qk_nope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) and qk_nope_head_dim "
                f"({self.qk_nope_head_dim}) must be even")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "GlmMoeConfig":
        """From a ``config.json`` of ``model_type`` ``glm4_moe_lite``."""
        return cls(**{k: config[k] for k in HF_KEYS if k in config}, **overrides)


def glm_moe_test_config(**overrides) -> GlmMoeConfig:
    """Every mechanism at a size for the CPU: rope and nope parts of unequal
    size, top-2 of 8 experts, one dense and two expert layers."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        num_nextn_predict_layers=1,
    )
    kwargs.update(overrides)
    return GlmMoeConfig(**kwargs)


def pairs_apart(kernel):
    """The stored (interleaved) rotary columns of a weight ``(..., rope)`` as
    the first of every pair and the second of every pair, ``(..., rope / 2)``
    each."""
    pairs = kernel.reshape(kernel.shape[:-1] + (kernel.shape[-1] // 2, 2))
    return pairs[..., 0], pairs[..., 1]


def _rotate(first, second, rope: int, theta: float, scale: float = 1.0):
    """The rotary embedding between two half heads ``(..., positions,
    width)``, in float32: the last ``rope / 2`` columns of ``first`` and of
    ``second`` are the first and the second of the rotary pairs and get the
    two numbers the interleaved rotary embedding gives the pair,
    the columns before them have no position; all times ``scale``."""
    t, width = first.shape[-2:]
    plain = width - rope // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    ang = jnp.arange(t).astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.ones((t, plain), jnp.float32), jnp.cos(ang)], axis=-1) * scale
    sin = jnp.concatenate([jnp.zeros((t, plain), jnp.float32), jnp.sin(ang)], axis=-1) * scale
    first, second = first.astype(jnp.float32), second.astype(jnp.float32)
    return first * cos - second * sin, second * cos + first * sin


def latent_qk(c_q, c_kv, k_rope, q_up, k_up, rope: int, theta: float, dtype):
    """The attention kernels' ``q`` (times ``1 / sqrt(head size)``) and ``k``,
    both ``(batch, heads, positions, nope + rope)``, with a head's columns in
    the order ``[nope a | pair firsts | nope b | pair seconds]`` (the module's
    text says why).  ``c_q`` and ``c_kv`` are the normed latents, ``k_rope
    (batch, positions, rope)`` the shared rotary key before rotation with its
    columns :func:`pairs_apart`; ``q_up (rank, heads, nope + rope)`` and
    ``k_up (rank, heads, nope)`` have the stored columns."""
    nope = k_up.shape[-1]
    a, half = nope // 2, (nope + rope) // 2
    firsts, seconds = pairs_apart(q_up[..., nope:])
    y = product(HEADS_MAJOR, c_q, jnp.concatenate(
        [q_up[..., :a], firsts, q_up[..., a:nope], seconds], axis=-1), dtype)
    q = jnp.concatenate(_rotate(y[..., :half], y[..., half:], rope, theta,
                                1.0 / math.sqrt(nope + rope)), axis=-1).astype(dtype)
    # the product writes k whole: zero columns in the weight where the rotary
    # columns go, and the one rotary key all heads share added to its result
    gap = ((0, 0), (0, 0), (0, rope // 2))
    k_up = jnp.concatenate([jnp.pad(k_up[..., :a], gap), jnp.pad(k_up[..., a:], gap)], axis=-1)
    shared = jnp.concatenate([
        jnp.pad(r.astype(dtype), ((0, 0), (0, 0), (a, 0)))
        for r in _rotate(k_rope[..., :rope // 2], k_rope[..., rope // 2:], rope, theta)], axis=-1)
    return q, product(HEADS_MAJOR, c_kv, k_up, dtype) + shared[:, None]


class LatentAttention(Kernels):
    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        hidden = x.shape[-1]
        heads, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                                 cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank = cfg.kv_lora_rank
        if nope + rope != dv:
            raise NotImplementedError(
                f"one head size for scores and values: {nope} + {rope} != {dv}")
        with model_scope("attn_proj"):
            q_down = self.kernel("q_down", hidden, cfg.q_lora_rank)
            q_up = self.kernel("q_up", cfg.q_lora_rank, heads * (nope + rope)).reshape(
                cfg.q_lora_rank, heads, nope + rope)
            kv_down = self.kernel("kv_down", hidden, rank + rope)
            kv_up = self.kernel("kv_up", rank, heads * (nope + dv)).reshape(rank, heads, nope + dv)
            out = self.kernel("out", heads * dv, hidden).reshape(heads, dv, hidden)
            c_q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(matmul(x, q_down, dt))
            down = matmul(x, jnp.concatenate(
                (kv_down[:, :rank],) + pairs_apart(kv_down[:, rank:]), axis=1), dt)
            c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(down[..., :rank])
            q, k = latent_qk(c_q, c_kv, down[..., rank:], q_up, kv_up[..., :nope],
                             rope, cfg.rope_theta, dt)
            v = product(HEADS_MAJOR, c_kv, kv_up[..., nope:], dt)
        with model_scope("attn_core"):
            ctx = causal_attention(q, k, v, 1.0)
        with model_scope("attn_proj"):
            return product("bhtd,hdm->btm", ctx, out, dt)


class SparseExperts(Kernels):
    """The router over all routed experts, the held experts' part of the
    routed result, and the shared expert."""

    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, hidden = x.shape
        experts, (first, count) = cfg.n_routed_experts, cfg.held
        width = cfg.moe_intermediate_size
        tokens = x.reshape(b * t, hidden)
        with model_scope("moe_route"):
            chosen, weights = sigmoid_topk_route(
                tokens, self.kernel("router", hidden, experts),
                self.kernel("correction_bias", experts),
                cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed = dropless_experts(
            tokens, chosen, weights,
            self.kernel("experts_gate", count, hidden, width),
            self.kernel("experts_up", count, hidden, width),
            self.kernel("experts_down", count, width, hidden),
            held=(first, count), num_experts=experts)
        with model_scope("moe_shared"):
            shared = SwiGLU(width * cfg.n_shared_experts, cfg.compute_dtype, name="shared")(x)
        return routed.reshape(b, t, hidden) + shared


class GlmMoeBlock(nn.Module):
    cfg: GlmMoeConfig
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x + LatentAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x))
        h = RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(x)
        if self.dense:
            with model_scope("dense_mlp"):
                return x + SwiGLU(cfg.intermediate_size, cfg.compute_dtype, name="mlp")(h)
        return x + SparseExperts(cfg, name="moe")(h)


class GlmMoeModel(Kernels):
    """``ids (batch, positions)`` to ``(logits, multi-token-prediction logits
    or None)``, both float32 ``(batch, positions, vocab)``.  Position ``i`` of
    the second predicts token ``i + 2``; its last two positions have no
    target."""

    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, ids):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        embedding = self.kernel("embedding", cfg.vocab_size, cfg.hidden_size)
        head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)

        def logits_of(h, norm_name):
            with model_scope("head"):
                h = RMSNorm(cfg.rms_norm_eps, name=norm_name)(h)
                return jnp.dot(h.astype(dt), head.astype(dt), preferred_element_type=jnp.float32)

        x = embed(embedding, ids, dt)
        for n in range(cfg.num_hidden_layers):
            x = GlmMoeBlock(cfg, dense=n < cfg.first_k_dense_replace, name=f"layer_{n}")(x)
        logits = logits_of(x, "final_norm")
        if not cfg.num_nextn_predict_layers:
            return logits, None
        if cfg.num_nextn_predict_layers != 1:
            raise NotImplementedError("multi-token prediction of depth 1 only")
        # the token after each position; the last position has none and is
        # no target's input (causal attention keeps it to itself)
        after = embed(embedding, jnp.roll(ids, -1, axis=1), dt)
        joined = jnp.concatenate([
            RMSNorm(cfg.rms_norm_eps, name="mtp_embed_norm")(after),
            RMSNorm(cfg.rms_norm_eps, name="mtp_hidden_norm")(x)], axis=-1)
        h = matmul(joined, self.kernel("mtp_proj", 2 * cfg.hidden_size, cfg.hidden_size), dt)
        h = GlmMoeBlock(cfg, dense=False, name="mtp_block")(h)
        return logits, logits_of(h, "mtp_final_norm")


def glm_moe_loss_fn(model: GlmMoeModel):
    """Next-token cross entropy, mean over each sequence's ``positions - 1``
    targets, plus ``mtp_loss_weight`` times the same for the token after
    next where the model predicts it.  ``batch`` is the ids alone."""
    weight = model.cfg.mtp_loss_weight

    def loss_fn(params, batch):
        logits, mtp_logits = model.apply({"params": params}, batch)
        # every row against the token that follows it, the rows without one
        # left out of the mean: a slice of the logits would be a copy of them
        loss = jnp.mean(softmax_cross_entropy(logits, jnp.roll(batch, -1, axis=1))[:, :-1])
        if mtp_logits is not None:
            loss = loss + weight * jnp.mean(
                softmax_cross_entropy(mtp_logits, jnp.roll(batch, -2, axis=1))[:, :-2])
        return loss

    return loss_fn
