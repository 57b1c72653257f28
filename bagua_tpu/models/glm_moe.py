"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a causal decoder with
multi-head *latent* attention, a leading dense layer, expert layers with a
sigmoid top-k router over all routed experts, a shared expert, and a
multi-token-prediction module.  Built from the keys of the published
``config.json`` (:meth:`GlmMoeConfig.from_hf`).

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
operands and accumulate in float32; norms, the router, the logits and the
loss are float32.  Each part of the forward pass sits under a
``bagua_model/part=...`` scope, so a device trace tells the parts apart.
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.models.llama import RMSNorm, apply_rope
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
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim ({self.qk_rope_head_dim}) must be even")

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


def _matmul(x, kernel, dtype):
    """``x @ kernel`` with ``dtype`` operands, float32 accumulation, ``dtype``
    result."""
    return jnp.dot(x.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


class _Kernels(nn.Module):
    """Float32 kernels, normal(0, 0.02), declared by shape."""

    def kernel(self, name: str, *shape: int):
        return self.param(name, nn.initializers.normal(0.02), shape, jnp.float32)


class LatentAttention(_Kernels):
    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        b, t, hidden = x.shape
        heads, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                                 cfg.qk_rope_head_dim, cfg.v_head_dim)
        if nope + rope != dv:
            raise NotImplementedError(
                f"one head size for scores and values: {nope} + {rope} != {dv}")
        with model_scope("attn_proj"):
            c_q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
                _matmul(x, self.kernel("q_down", hidden, cfg.q_lora_rank), dt))
            q = _matmul(c_q, self.kernel("q_up", cfg.q_lora_rank, heads * (nope + rope)), dt)
            q = q.reshape(b, t, heads, nope + rope)
            down = _matmul(x, self.kernel("kv_down", hidden, cfg.kv_lora_rank + rope), dt)
            c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_norm")(down[..., :cfg.kv_lora_rank])
            kv = _matmul(c_kv, self.kernel("kv_up", cfg.kv_lora_rank, heads * (nope + dv)), dt)
            kv = kv.reshape(b, t, heads, nope + dv)
            positions = jnp.arange(t)
            q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
            k_rope = apply_rope(down[..., None, cfg.kv_lora_rank:], positions, cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, heads, rope))], axis=-1)
            q, k, v = (y.transpose(0, 2, 1, 3) for y in (q, k, kv[..., nope:]))
        with model_scope("attn_core"):
            ctx = causal_attention(q, k, v, 1.0 / math.sqrt(nope + rope))
        with model_scope("attn_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, heads * dv)
            return _matmul(ctx, self.kernel("out", heads * dv, hidden), dt)


class SwiGLU(_Kernels):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        h = jax.nn.silu(_matmul(x, self.kernel("gate", hidden, self.width), self.dtype))
        h = h * _matmul(x, self.kernel("up", hidden, self.width), self.dtype)
        return _matmul(h, self.kernel("down", self.width, hidden), self.dtype)


class SparseExperts(_Kernels):
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


class GlmMoeModel(_Kernels):
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

        x = embedding[ids].astype(dt)
        for n in range(cfg.num_hidden_layers):
            x = GlmMoeBlock(cfg, dense=n < cfg.first_k_dense_replace, name=f"layer_{n}")(x)
        logits = logits_of(x, "final_norm")
        if not cfg.num_nextn_predict_layers:
            return logits, None
        if cfg.num_nextn_predict_layers != 1:
            raise NotImplementedError("multi-token prediction of depth 1 only")
        # the token after each position; the last position has none and is
        # no target's input (causal attention keeps it to itself)
        after = embedding[jnp.roll(ids, -1, axis=1)].astype(dt)
        joined = jnp.concatenate([
            RMSNorm(cfg.rms_norm_eps, name="mtp_embed_norm")(after),
            RMSNorm(cfg.rms_norm_eps, name="mtp_hidden_norm")(x)], axis=-1)
        h = _matmul(joined, self.kernel("mtp_proj", 2 * cfg.hidden_size, cfg.hidden_size), dt)
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
