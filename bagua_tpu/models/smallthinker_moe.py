"""SmallThinker-21BA3B-Instruct (``model_type`` ``smallthinker``,
arXiv:2507.20984): a causal decoder whose attention layers are of two kinds in
one stack, and whose router is fed before attention.  Built from the keys of
the published ``config.json`` (:meth:`SmallThinkerConfig.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`, the one lookup
of the three expert models, which also says what an id outside the vocabulary
does).  Per layer ``n``, on the residual stream ``x`` (RMSNorm with a learned
scale, no bias anywhere, no norm on heads):

* ``h = RMSNorm(x)``.
* the router, **before attention**, from ``h``:
  :func:`~bagua_tpu.parallel.moe.dropless.softmax_topk_route`: the
  ``moe_num_active_primary_experts`` largest of ``h W_r`` and a softmax over
  those alone (``moe_primary_router_apply_softmax``; ``norm_topk_prob``
  divides by their sum, which is one).  Routing waits for no attention.
* attention from the same ``h``: ``q`` onto ``num_attention_heads`` heads and
  ``k``, ``v`` onto ``num_key_value_heads`` heads of ``head_dim`` columns; where
  ``rope_layout[n]`` is 1 the rotary embedding on all columns of ``q`` and
  ``k`` in the rotate-half pairing, where it is 0 no position at all; each
  key-value head serves ``heads / kv heads`` query heads under the causal mask
  and, where ``sliding_window_layout[n]`` is 1, a window of
  ``sliding_window_size`` keys that counts the current position
  (:func:`~bagua_tpu.kernels.causal_attention.causal_attention`); ``W_o``;
  ``x += ...``.
* experts: ``x += sum_j w_j E_j(RMSNorm(x))`` over the chosen experts this
  chip *holds* (``experts_held``,
  :func:`~bagua_tpu.parallel.moe.dropless.dropless_experts`), ``E(u) =
  W_down(relu(W_gate u) * W_up u)`` of ``moe_ffn_hidden_size``.  Every layer
  has experts; there is no dense layer and no shared expert.
* head: ``RMSNorm(x) W_head``, a matrix of its own (``tie_word_embeddings``
  false).

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the rotation, the router, the
logits and the loss are float32.  Each part of the forward pass sits under a
``bagua_model/part=...`` scope; the core of a windowed layer under
``attn_window_core`` and of a global one under ``attn_core``, so that the two
masks are read apart.

The parts shared with the other decoder models are ``models/decoder.py``'s
(``RMSNorm``, ``Kernels``, the next-token loss, and ``GroupedQueryAttention``
with ``rope_theta`` or none and ``window`` or none by the layer's two keys).
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.models.decoder import GroupedQueryAttention, Kernels, RMSNorm, next_token_loss_fn
from bagua_tpu.models.embedding import embed
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, softmax_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "head_dim", "moe_ffn_hidden_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax", "norm_topk_prob",
    "sliding_window_layout", "sliding_window_size", "rope_layout", "rope_theta", "rms_norm_eps",
    "tie_word_embeddings",
)
#: the published pattern, in both lists: a global layer without positions,
#: then three windowed layers with the rotary embedding, thirteen times
PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    sliding_window_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    sliding_window_size: int = 4096
    rope_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    #: ``(first, count)`` of the experts whose kernels live here; None: all
    experts_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        for name in ("sliding_window_layout", "rope_layout"):
            layout = tuple(getattr(self, name))
            object.__setattr__(self, name, layout)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} {layout} is no 0 or 1 for each of {self.num_hidden_layers} layers")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.moe_num_primary_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.moe_num_primary_experts} experts")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError(
                f"num_key_value_heads ({self.num_key_value_heads}) must divide "
                f"num_attention_heads ({self.num_attention_heads}), and head_dim "
                f"({self.head_dim}) be even")
        if not self.moe_primary_router_apply_softmax:
            raise NotImplementedError(
                "moe_primary_router_apply_softmax false: the published model has it true")
        if self.tie_word_embeddings:
            raise NotImplementedError("tie_word_embeddings: the published model has its own head")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.moe_num_primary_experts)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "SmallThinkerConfig":
        """From a ``config.json`` of ``model_type`` ``smallthinker``."""
        return cls(**{k: config[k] for k in HF_KEYS if k in config}, **overrides)


def smallthinker_test_config(**overrides) -> SmallThinkerConfig:
    """Every mechanism at a size for the CPU: a global layer without
    positions and a windowed one with them, a window shorter than the
    sequences the tests use, three query heads a key-value head, top-3 of 8
    experts."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, head_dim=8, moe_ffn_hidden_size=16, num_hidden_layers=2,
        num_attention_heads=6, num_key_value_heads=2, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, sliding_window_layout=(0, 1), sliding_window_size=8,
        rope_layout=(0, 1),
    )
    kwargs.update(overrides)
    return SmallThinkerConfig(**kwargs)


class WindowOrGlobalAttention(GroupedQueryAttention):
    """The shared layer with the kernel looked up under this module's name for
    it: ``tests/benchmark`` puts a kernel that drops the window there."""

    @nn.nowrap
    def core(self, q, k, v):
        with model_scope("attn_core" if self.window is None else "attn_window_core"):
            return causal_attention(q, k, v, 1.0, window=self.window)


class SmallThinkerBlock(Kernels):
    cfg: SmallThinkerConfig
    windowed: bool
    rotary: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, hidden = x.shape
        experts, (first, count) = cfg.moe_num_primary_experts, cfg.held
        width = cfg.moe_ffn_hidden_size
        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x)
        with model_scope("moe_route"):
            # from the attention's input: the choice is made before attention runs
            chosen, weights = softmax_topk_route(
                h.reshape(b * t, hidden), self.kernel("router", hidden, experts),
                cfg.moe_num_active_primary_experts)
        x = x + WindowOrGlobalAttention(
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.compute_dtype,
            rope_theta=cfg.rope_theta if self.rotary else None,
            window=cfg.sliding_window_size if self.windowed else None, name="attn")(h)
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(x)
        routed = dropless_experts(
            h.reshape(b * t, hidden), chosen, weights,
            self.kernel("experts_gate", count, hidden, width),
            self.kernel("experts_up", count, hidden, width),
            self.kernel("experts_down", count, width, hidden),
            held=(first, count), num_experts=experts, activation=jax.nn.relu)
        return x + routed.reshape(b, t, hidden)


class SmallThinkerModel(Kernels):
    """``ids (batch, positions)`` to float32 logits ``(batch, positions,
    vocab)`` through the output matrix."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, ids):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        x = embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dt)
        for n, (windowed, rotary) in enumerate(zip(cfg.sliding_window_layout, cfg.rope_layout)):
            x = SmallThinkerBlock(cfg, bool(windowed), bool(rotary), name=f"layer_{n}")(x)
        with model_scope("head"):
            h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            return jnp.einsum("btm,mv->btv", h.astype(dt), head.astype(dt),
                              preferred_element_type=jnp.float32)


smallthinker_loss_fn = next_token_loss_fn
