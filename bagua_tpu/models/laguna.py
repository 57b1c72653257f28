"""Laguna-XS.2 (``model_type`` ``laguna``, poolside, 33.4B-A3B): a causal
decoder whose attention layers are of two kinds *with two head counts* in one
stack, each with a per-head output gate, over a leading dense layer and
sigmoid-routed experts with a shared one.  Built from the keys of the published
``config.json`` (:meth:`LagunaConfig.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`).  Layer ``n``, on
the residual stream ``x`` (RMSNorm with a learned scale, no bias anywhere, no
norm on heads):

* ``h = RMSNorm(x)``; ``q`` onto ``num_attention_heads_per_layer[n]`` heads,
  ``k`` and ``v`` onto ``num_key_value_heads`` heads of ``head_dim`` columns,
  each key-value head serving ``heads / kv heads`` query heads: 48 on 8 in a
  ``full_attention`` layer, 64 on 8 in a ``sliding_attention`` layer, so the
  stack holds two shapes of ``q_proj``, ``out_proj`` and ``gate_proj``.
* the rotary embedding by ``rope_parameters[layer_types[n]]``, rotate-half
  pairing inside the rotated columns (:class:`RopeParameters`,
  :func:`yarn_inv_freq`): ``sliding_attention`` all columns at ``rope_theta``
  10,000; ``full_attention`` the first ``head_dim x partial_rotary_factor``
  columns with YaRN-blended frequencies and ``cos``/``sin`` times
  ``attention_factor``, the other columns passing through unrotated.
* ``ctx = softmax(q k^T / sqrt(head_dim) + mask) v``, the mask causal and, in
  a ``sliding_attention`` layer, a window of ``sliding_window`` keys that
  counts the current position
  (:func:`~bagua_tpu.kernels.causal_attention.causal_attention`).
* the gate (``gating``): ``a = sigmoid(h W_g)``, one scalar a head and
  position from the same normed input; ``ctx[head] *= a[head]``; ``x += ctx
  W_o``.
* ``u = RMSNorm(x)``; where ``mlp_layer_types[n]`` is ``dense`` ``x +=
  SwiGLU(u)`` of ``intermediate_size``, else
  :func:`~bagua_tpu.parallel.moe.dropless.sigmoid_topk_route` over all
  ``num_experts`` outputs (``num_experts_per_tok`` chosen by ``s + b``, weights
  normalised over the chosen and times ``moe_routed_scaling_factor``) and ``x
  += shared(u) + sum_j w_j E_j(u)`` over the chosen experts this chip *holds*
  (``experts_held``,
  :func:`~bagua_tpu.parallel.moe.dropless.dropless_experts`), ``shared`` and
  ``E_j`` SwiGLU units of ``shared_expert_intermediate_size`` and
  ``moe_intermediate_size``.
* head: ``RMSNorm(x) W_head``, a matrix of its own (``tie_word_embeddings``
  false).

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the rotation, the gate's sigmoid,
the router, the logits and the loss are float32.  Each part of the forward pass
sits under a ``bagua_model/part=...`` scope; the gate's product, sigmoid and
multiplication under ``attn_gate``.

The parts shared with the other decoder models are ``models/decoder.py``'s
(``RMSNorm``, ``Kernels``, ``SwiGLU``, the next-token loss, and
``GroupedQueryAttention`` with heads, tables, window and gate by the layer).
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.models.decoder import (
    GroupedQueryAttention,
    Kernels,
    RMSNorm,
    RotaryTables,
    SwiGLU,
    next_token_loss_fn,
)
from bagua_tpu.models.embedding import embed
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_key_value_heads",
    "head_dim", "rms_norm_eps", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "tie_word_embeddings", "gating", "sliding_window",
    "rope_parameters", "layer_types", "mlp_layer_types", "moe_routed_scaling_factor",
    "num_attention_heads_per_layer", "moe_apply_router_weight_on_input", "attention_bias",
)
FULL, SLIDING = "full_attention", "sliding_attention"
#: the published pattern: a global layer of 48 query heads, then three
#: windowed layers of 64, ten times; the first layer's MLP dense
PUBLISHED_LAYER_TYPES = (FULL, SLIDING, SLIDING, SLIDING) * 10
PUBLISHED_HEADS = (48, 64, 64, 64) * 10
PUBLISHED_MLP_TYPES = ("dense",) + ("sparse",) * 39


def yarn_inv_freq(dim: int, base: float, factor: float, original_max_position_embeddings: int,
                  beta_fast: float, beta_slow: float):
    """``(inv_freq, low, high)`` of YaRN as ``transformers``'
    ``_compute_yarn_parameters`` gives them for ``dim`` rotated columns:
    ``inv_freq_i = (1 - r_i) / (factor base^(2i/dim)) + r_i / base^(2i/dim)``
    with ``r_i = 1 - clip((i - low) / (high - low), 0, 1)``, ``low`` and
    ``high`` the pairs that make ``beta_fast`` and ``beta_slow`` turns over the
    original context, ``c(m) = dim ln(original / (2 pi m)) / (2 ln base)``,
    rounded outwards (``truncate`` at its default) and held to ``0 .. dim - 1``."""
    def pair_of(turns):
        return dim * math.log(original_max_position_embeddings / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    span = high - low if high != low else 0.001
    inv_freq = []
    for i in range(dim // 2):
        plain = base ** (-2.0 * i / dim)
        r = 1.0 - min(max((i - low) / span, 0.0), 1.0)
        inv_freq.append((1.0 - r) * plain / factor + r * plain)
    return tuple(inv_freq), low, high


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """One entry of ``rope_parameters``: ``rope_type`` ``default`` or
    ``yarn``, the latter with its five keys."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: Optional[float] = None
    original_max_position_embeddings: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise NotImplementedError(f"rope_type {self.rope_type!r}")
        if self.rope_type == "yarn" and None in (self.factor, self.original_max_position_embeddings):
            raise ValueError("yarn needs factor and original_max_position_embeddings")

    def of_layer(self, head_dim: int) -> dict:
        """What :class:`~bagua_tpu.models.decoder.GroupedQueryAttention` takes
        for it: ``rope_theta`` where every column turns at the plain
        frequencies, the tables where not."""
        columns = int(head_dim * self.partial_rotary_factor)
        if columns % 2 or not 0 < columns <= head_dim:
            raise ValueError(f"{columns} rotated columns of a head of {head_dim}")
        if self.rope_type == "default":
            if columns == head_dim:
                return {"rope_theta": self.rope_theta}
            return {"rope": RotaryTables(tuple(
                self.rope_theta ** (-2.0 * i / columns) for i in range(columns // 2)))}
        inv_freq, _, _ = yarn_inv_freq(
            columns, self.rope_theta, self.factor, self.original_max_position_embeddings,
            self.beta_fast, self.beta_slow)
        # the family's default where the key is absent: 0.1 ln(factor) + 1
        factor = self.attention_factor or 0.1 * math.log(self.factor) + 1.0
        return {"rope": RotaryTables(inv_freq, factor)}


def _published_rope_parameters():
    return ((FULL, RopeParameters(
        "yarn", 500000.0, 0.5, 64.0, 4096, 64.0, 1.0, 1.4158883083359672)),
        (SLIDING, RopeParameters("default", 10000.0, 1.0)))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    #: ``{layer type: its entry}`` of ``config.json``, kept as pairs
    rope_parameters: Any = dataclasses.field(default_factory=_published_rope_parameters)
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    mlp_layer_types: Tuple[str, ...] = PUBLISHED_MLP_TYPES
    moe_routed_scaling_factor: float = 2.5
    num_attention_heads_per_layer: Tuple[int, ...] = PUBLISHED_HEADS
    moe_apply_router_weight_on_input: bool = False
    attention_bias: bool = False
    #: ``(first, count)`` of the experts whose kernels live here; None: all
    experts_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", tuple(
                (kind, RopeParameters(**entry))
                for kind, entry in self.rope_parameters.items() if isinstance(entry, dict)))
        for name, allowed in (("layer_types", {FULL, SLIDING}),
                              ("mlp_layer_types", {"dense", "sparse"})):
            kinds = tuple(getattr(self, name))
            object.__setattr__(self, name, kinds)
            if len(kinds) != self.num_hidden_layers or set(kinds) - allowed:
                raise ValueError(f"{name} {kinds} is no one of {sorted(allowed)} for each of "
                                 f"{self.num_hidden_layers} layers")
        heads = tuple(self.num_attention_heads_per_layer)
        object.__setattr__(self, "num_attention_heads_per_layer", heads)
        if len(heads) != self.num_hidden_layers or any(
                n < 1 or n % self.num_key_value_heads for n in heads):
            raise ValueError(
                f"num_attention_heads_per_layer {heads} is no multiple of the "
                f"{self.num_key_value_heads} key-value heads for each of "
                f"{self.num_hidden_layers} layers")
        if set(self.layer_types) - {kind for kind, _ in self.rope_parameters}:
            raise ValueError(f"rope_parameters has no entry for each of {set(self.layer_types)}")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range of the "
                             f"{self.num_experts} experts")
        if self.tie_word_embeddings or self.attention_bias or self.moe_apply_router_weight_on_input:
            raise NotImplementedError(
                "tie_word_embeddings, attention_bias, moe_apply_router_weight_on_input: the "
                "published model has each false")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    def rotary(self, layer_type: str) -> dict:
        return dict(self.rope_parameters)[layer_type].of_layer(self.head_dim)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "LagunaConfig":
        """From a ``config.json`` of ``model_type`` ``laguna``."""
        return cls(**{k: config[k] for k in HF_KEYS if k in config}, **overrides)


def laguna_test_config(**overrides) -> LagunaConfig:
    """Every mechanism at a size for the CPU: a global layer of 4 query heads
    over a dense MLP, a windowed layer of 6 over experts and a global one over
    experts, on 2 key-value heads; a window shorter than the sequences the
    tests use; YaRN on half of a head's 16 columns with both ends of its ramp
    inside the 4 pairs; top-3 of 8 experts and a shared one."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=3,
        num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=16, shared_expert_intermediate_size=16, sliding_window=8,
        rope_parameters={
            FULL: dict(rope_type="yarn", rope_theta=100.0, factor=8.0,
                       original_max_position_embeddings=16, beta_fast=2.0, beta_slow=0.25,
                       partial_rotary_factor=0.5),
            SLIDING: dict(rope_type="default", rope_theta=10000.0, partial_rotary_factor=1.0)},
        layer_types=(FULL, SLIDING, FULL), mlp_layer_types=("dense", "sparse", "sparse"),
        num_attention_heads_per_layer=(4, 6, 4),
    )
    kwargs.update(overrides)
    return LagunaConfig(**kwargs)


class LagunaAttention(GroupedQueryAttention):
    """The shared layer with the kernel looked up under this module's name for
    it: ``tests/benchmark`` puts a kernel that drops the window there."""

    @nn.nowrap
    def core(self, q, k, v):
        with model_scope("attn_core" if self.window is None else "attn_window_core"):
            return causal_attention(q, k, v, 1.0, window=self.window)


class SparseExperts(Kernels):
    """The router over all routed experts, the held experts' part of the
    routed result, and the shared expert."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, hidden = x.shape
        experts, (first, count) = cfg.num_experts, cfg.held
        width = cfg.moe_intermediate_size
        tokens = x.reshape(b * t, hidden)
        with model_scope("moe_route"):
            chosen, weights = sigmoid_topk_route(
                tokens, self.kernel("router", hidden, experts),
                self.kernel("correction_bias", experts),
                cfg.num_experts_per_tok, cfg.moe_routed_scaling_factor)
        routed = dropless_experts(
            tokens, chosen, weights,
            self.kernel("experts_gate", count, hidden, width),
            self.kernel("experts_up", count, hidden, width),
            self.kernel("experts_down", count, width, hidden),
            held=(first, count), num_experts=experts)
        with model_scope("moe_shared"):
            shared = SwiGLU(cfg.shared_expert_intermediate_size, cfg.compute_dtype, name="shared")(x)
        return routed.reshape(b, t, hidden) + shared


class LagunaBlock(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg, n = self.cfg, self.layer
        kind = cfg.layer_types[n]
        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x)
        x = x + LagunaAttention(
            cfg.num_attention_heads_per_layer[n], cfg.num_key_value_heads, cfg.head_dim,
            cfg.compute_dtype, window=cfg.sliding_window if kind == SLIDING else None,
            gate=cfg.gating, name="attn", **cfg.rotary(kind))(h)
        u = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(x)
        if cfg.mlp_layer_types[n] == "dense":
            with model_scope("dense_mlp"):
                return x + SwiGLU(cfg.intermediate_size, cfg.compute_dtype, name="mlp")(u)
        return x + SparseExperts(cfg, name="moe")(u)


class LagunaModel(Kernels):
    """``ids (batch, positions)`` to float32 logits ``(batch, positions,
    vocab)`` through the output matrix."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, ids):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        x = embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dt)
        for n in range(cfg.num_hidden_layers):
            x = LagunaBlock(cfg, n, name=f"layer_{n}")(x)
        with model_scope("head"):
            h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            return jnp.einsum("btm,mv->btv", h.astype(dt), head.astype(dt),
                              preferred_element_type=jnp.float32)


laguna_loss_fn = next_token_loss_fn
