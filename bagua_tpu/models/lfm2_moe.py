"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``): a causal decoder whose layers
take one of two mixers, a gated short convolution or grouped-query attention
with normed heads, and one of two feed-forward parts, a dense SwiGLU in the
leading layers and a sigmoid top-k router over all routed experts with no
shared expert in the rest.  Built from the keys of the published
``config.json`` (:meth:`Lfm2MoeConfig.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`, the one lookup
of the three expert models, which also says what an id outside the vocabulary
does).  Per layer, on the residual stream ``x`` (RMSNorm with a learned scale,
no bias anywhere): ``x += Mixer(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``.

* ``layer_types[n] == "conv"``: ``[B | C | u] = h W_in``; ``z = B * u``;
  ``c_t = sum_j taps[j] * z_{t-j}`` per channel over ``conv_L_cache`` taps
  (depthwise, causal: ``z`` before position 0 is zero); ``y = (C * c) W_out``
  (:func:`gated_short_conv` is gates and taps).
* ``"full_attention"``: ``q``, ``k``, ``v`` projections onto
  ``num_attention_heads`` query heads and ``num_key_value_heads`` key-value
  heads of ``hidden_size / num_attention_heads`` columns; RMSNorm over the
  columns of every head of ``q`` and of ``k`` (one learned scale each); the
  rotary embedding on all columns in the rotate-half pairing (column ``i``
  with ``i + size / 2``); each key-value head serves ``heads / kv heads``
  query heads (:func:`~bagua_tpu.kernels.causal_attention.causal_attention`);
  ``W_o`` (``decoder.GroupedQueryAttention`` with ``norm_eps`` and ``rope_theta``).
* the first ``num_dense_layers``: SwiGLU of ``intermediate_size``.
* the rest: :func:`~bagua_tpu.parallel.moe.dropless.sigmoid_topk_route` over
  all ``num_experts`` (``use_expert_bias``: a bias that steers the choice and
  takes no gradient) and the part of the chosen experts this chip *holds*
  (``experts_held``,
  :func:`~bagua_tpu.parallel.moe.dropless.dropless_experts`), each a SwiGLU of
  ``moe_intermediate_size``.
* head: ``RMSNorm(x) Emb^T``: the output matrix is the embedding, one leaf
  with two gradients (the lookup's, a float32 array of the table's shape
  whatever form it was built in, and the product's).

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the rotation, the gates and taps,
the router, the logits and the loss are float32.  Each part of the forward
pass sits under a ``bagua_model/part=...`` scope (``conv_proj``: the
mixer's two products; ``conv_core``: gates and taps).

The parts shared with the other decoder models are ``models/decoder.py``'s
(``RMSNorm``, ``Kernels``, ``matmul``, ``SwiGLU``, ``shift``, the attention
layer and how its operands are written, the next-token loss).

How the operands are written (``PERF.md`` section 6, PR 33).  The short
convolution is one pass forward (it reads ``[B | C | u]`` as the
product wrote it and writes ``C * c`` where the next product reads it) and
one pass backward that builds ``z`` and ``c`` again from ``[B | C | u]`` and
writes ``[dB | dC | du]`` as one array, where the product's two gradients
read it: nothing but the product's result is kept for the backward pass.
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.models.decoder import (
    GroupedQueryAttention, Kernels, RMSNorm, SwiGLU, matmul, next_token_loss_fn, shift)
from bagua_tpu.models.embedding import embed
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "conv_L_cache", "conv_bias", "num_experts", "num_experts_per_tok",
    "routed_scaling_factor", "norm_topk_prob", "use_expert_bias", "rope_theta", "norm_eps",
)
MIXERS = ("conv", "full_attention")
#: the published pattern: 18 short convolutions, attention in six places
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if n in (2, 6, 10, 14, 18, 21) else "conv" for n in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    #: added to the sum of the chosen scores before the division; not in
    #: ``config.json`` (the family's code: 1e-6)
    router_eps: float = 1e-6
    #: ``(first, count)`` of the routed experts whose kernels live here;
    #: None: all of them
    experts_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(MIXERS):
            raise ValueError(
                f"layer_types {self.layer_types} does not name one of {MIXERS} for each of "
                f"{self.num_hidden_layers} layers")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the {self.num_experts} "
                "routed experts")
        if self.hidden_size % self.num_attention_heads or self.head_size % 2:
            raise ValueError(
                f"hidden_size ({self.hidden_size}) must divide into num_attention_heads "
                f"({self.num_attention_heads}) heads of even size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_key_value_heads ({self.num_key_value_heads}) must divide "
                f"num_attention_heads ({self.num_attention_heads})")
        if self.conv_bias:
            raise NotImplementedError("conv_bias: the published model has none")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "Lfm2MoeConfig":
        """From a ``config.json`` of ``model_type`` ``lfm2_moe``."""
        return cls(**{k: config[k] for k in HF_KEYS if k in config}, **overrides)


def lfm2_moe_test_config(**overrides) -> Lfm2MoeConfig:
    """Every mechanism at a size for the CPU: a dense layer with a short
    convolution, an attention and a convolution layer with experts, two query
    heads a key-value head, top-2 of 8 experts."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_dense_layers=1, layer_types=("conv", "full_attention", "conv"),
        num_attention_heads=4, num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
    )
    kwargs.update(overrides)
    return Lfm2MoeConfig(**kwargs)


# -- the gated short convolution ----------------------------------------------


def _gates_and_taps(bcu, taps):
    f32 = jnp.float32
    gate_b, gate_c, u = (part.astype(f32) for part in jnp.split(bcu, 3, axis=-1))
    z = gate_b * u
    c = sum(taps[j].astype(f32) * shift(z, j) for j in range(taps.shape[0]))
    return gate_b, gate_c, u, z, c


@jax.custom_vjp
def gated_short_conv(bcu, taps):
    """``C * c`` with ``c_t = sum_j taps[j] * (B * u)_{t-j}``, ``(batch,
    positions, channels)`` in ``bcu``'s type, from ``bcu = [B | C | u]``
    ``(batch, positions, 3 x channels)`` and ``taps (L, channels)``; computed
    in float32 and rounded once.  The backward pass keeps ``bcu`` and the
    taps alone and builds ``z`` and ``c`` again."""
    _, gate_c, _, _, c = _gates_and_taps(bcu, taps)
    return (gate_c * c).astype(bcu.dtype)


def _gated_short_conv_fwd(bcu, taps):
    return gated_short_conv(bcu, taps), (bcu, taps)


def _gated_short_conv_bwd(res, dy):
    bcu, taps = res
    gate_b, gate_c, u, z, c = _gates_and_taps(bcu, taps)
    dy = dy.astype(jnp.float32)
    dc = dy * gate_c
    # z_t feeds c_{t+j} through tap j: the taps run against time
    dz = sum(taps[j].astype(jnp.float32) * shift(dc, -j) for j in range(taps.shape[0]))
    d_taps = jnp.stack([jnp.sum(dc * shift(z, j), axis=(0, 1)) for j in range(taps.shape[0])])
    d_bcu = jnp.concatenate([dz * u, dy * c, dz * gate_b], axis=-1).astype(bcu.dtype)
    return d_bcu, d_taps.astype(taps.dtype)


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


class ShortConv(Kernels):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        hidden = x.shape[-1]
        with model_scope("conv_proj"):
            bcu = matmul(x, self.kernel("in_proj", hidden, 3 * hidden), dt)
        with model_scope("conv_core"):
            # the taps at a scale that keeps the mixer's output near its input's
            taps = self.param("taps", nn.initializers.normal(cfg.conv_L_cache ** -0.5),
                              (cfg.conv_L_cache, hidden), jnp.float32)
            y = gated_short_conv(bcu, taps)
        with model_scope("conv_proj"):
            return matmul(y, self.kernel("out_proj", hidden, hidden), dt)


# -- the expert layer ---------------------------------------------------------


class RoutedExperts(Kernels):
    """The router over all routed experts and the held experts' part of the
    routed result; no shared expert."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, hidden = x.shape
        experts, (first, count) = cfg.num_experts, cfg.held
        width = cfg.moe_intermediate_size
        tokens = x.reshape(b * t, hidden)
        with model_scope("moe_route"):
            bias = (self.kernel("expert_bias", experts) if cfg.use_expert_bias
                    else jnp.zeros((experts,), jnp.float32))
            chosen, weights = sigmoid_topk_route(
                tokens, self.kernel("router", hidden, experts), bias, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.router_eps)
        routed = dropless_experts(
            tokens, chosen, weights,
            self.kernel("experts_gate", count, hidden, width),
            self.kernel("experts_up", count, hidden, width),
            self.kernel("experts_down", count, width, hidden),
            held=(first, count), num_experts=experts)
        return routed.reshape(b, t, hidden)


class Lfm2MoeBlock(nn.Module):
    cfg: Lfm2MoeConfig
    mixer: str
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        if self.mixer == "conv":
            x = x + ShortConv(cfg, name="conv")(h)
        else:
            x = x + GroupedQueryAttention(
                cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size, cfg.compute_dtype,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta, name="attn")(h)
        h = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
        if self.dense:
            with model_scope("dense_mlp"):
                return x + SwiGLU(cfg.intermediate_size, cfg.compute_dtype, name="mlp")(h)
        return x + RoutedExperts(cfg, name="moe")(h)


class Lfm2MoeModel(Kernels):
    """``ids (batch, positions)`` to float32 logits ``(batch, positions,
    vocab)`` through the embedding's transpose."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, ids):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        embedding = self.kernel("embedding", cfg.vocab_size, cfg.hidden_size)
        x = embed(embedding, ids, dt)
        for n, mixer in enumerate(cfg.layer_types):
            x = Lfm2MoeBlock(cfg, mixer, dense=n < cfg.num_dense_layers, name=f"layer_{n}")(x)
        with model_scope("head"):
            h = RMSNorm(cfg.norm_eps, name="final_norm")(x)
            return jnp.einsum("btm,vm->btv", h.astype(dt), embedding.astype(dt),
                              preferred_element_type=jnp.float32)


lfm2_moe_loss_fn = next_token_loss_fn
