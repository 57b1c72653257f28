"""Solar-Open2-250B (``model_type`` ``solar_open2``, upstage, 250B-A15B): a
causal decoder whose mixers are of two kinds in one stack, three gated
delta-rule linear-attention layers (Kimi Delta Attention, KDA, arXiv:2510.26692)
to one gated grouped-query attention layer without positions, each over
sigmoid-routed experts with a shared one.  Built from the keys of the published
``config.json`` (:meth:`SolarOpen2Config.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`).  RMSNorm with a
learned scale, no bias but the KDA gate's, no positions anywhere (``use_rope``
false).  Every layer: ``h = RMSNorm(x)``, ``x += mixer(h)``, ``u = RMSNorm(x)``,
``x += experts(u)``.

* the GQA mixer (layers in ``gqa_layers``): ``q`` onto ``num_attention_heads``
  heads and ``k``, ``v`` onto ``num_key_value_heads`` heads of ``head_dim``,
  ``ctx = softmax(q k^T / sqrt(head_dim) + causal) v``; the gate
  (``use_gqa_gate``) ``a = sigmoid(h W_g)`` from the same normed input, one
  value a head *column* (``W_g`` of ``hidden x heads x head_dim``), ``ctx *=
  a``; ``mixer = ctx W_o``
  (:class:`~bagua_tpu.models.decoder.GroupedQueryAttention` with
  ``gate="column"``).
* the KDA mixer (every other layer), ``H`` heads of ``d = head_dim`` keys and
  values (``linear_attn_config``; ``num_kv_heads`` null: keys and values have
  the query's heads), ``conv`` the depthwise causal convolution of
  ``short_conv_kernel_size`` taps a column, no bias
  (:func:`~bagua_tpu.models.decoder.causal_conv_silu`): ``q~ = silu(conv(h
  W_q))``, ``k~``, ``v`` alike; a head's ``q = q~ / ||q~||_2 / sqrt(d)``, ``k =
  k~ / ||k~||_2`` (``eps`` 1e-6 under the root); the decay a *channel*
  (``kda_use_full_proj`` false: two products through the head size), ``g =
  -exp(A_log[head]) softplus((h W_f1) W_f2 + dt_bias)``; ``beta = 2 sigmoid(h
  W_b)``, one a head and position (``kda_allow_neg_eigval`` doubles it); the
  recurrence a head from ``S_0 = 0``, ``S' = Diag(exp(g_t)) S_{t-1}``, ``S_t =
  S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``
  (:func:`~bagua_tpu.kernels.delta_rule.gated_delta_rule`, in chunks of
  ``chunk_size``); ``y = RMSNorm_d(o_t) * sigmoid((h W_g1) W_g2 + b_g)``, the
  norm's scale of ``d`` shared by the heads; ``mixer = y W_o``.
* the experts (every layer; ``first_k_dense_replace`` 0):
  :func:`~bagua_tpu.parallel.moe.dropless.sigmoid_topk_route` over all
  ``n_routed_experts`` outputs (``num_experts_per_tok`` chosen by ``s + b``,
  weights normalised over the chosen, ``norm_topk_prob``, and times
  ``routed_scaling_factor``), ``experts = shared(u) + sum_j w_j E_j(u)`` over
  the chosen experts this chip *holds*
  (:func:`~bagua_tpu.parallel.moe.dropless.dropless_experts`), ``shared`` and
  ``E_j`` SwiGLU units of ``moe_intermediate_size``.
* head: ``RMSNorm(x) W_head``, a matrix of its own (``tie_word_embeddings``
  false).  ``intermediate_size`` is read by no layer.

**What this chip holds.**  Two ranges, ``(first, count)`` each, ``None`` for
all: ``experts_held`` of the routed experts (the router keeps its width, its
choices and its normalisation; the terms of the experts held elsewhere are
left out) and ``heads_held`` of each mixer's heads: the query heads with the
key-value heads they read and the gate's columns, and the KDA heads with their
columns of ``W_q``, ``W_k``, ``W_v``, ``W_f2``, ``W_g2``, the taps, ``dt_bias``,
``A_log``, ``W_b``, the gate's bias and ``W_o``'s rows.  ``W_f1``, ``W_g1``, the
head norm's scale, the router, the shared expert and the norms are whole on
every chip.  A mixer's result is then this chip's part of the sum over heads
that ``W_o`` takes, and the shares of all chips add up to the whole
(``tests/test_solar_open2.py``).  No stand-in for the other chips.

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the L2 norms, ``g``, ``beta``, the
running sums, decays and state of the delta rule, the gates' sigmoids, the
router, the logits and the loss are float32.  Each part of the forward pass
sits under a ``bagua_model/part=...`` scope: ``kda_proj`` (the four wide
products and the four narrow ones), ``kda_conv`` (three convolutions with
SiLU), ``kda_core`` (L2 norms, decay, ``beta`` and the chunked delta rule),
``kda_gate_norm`` (head norm and gate), beside ``attn_proj``, ``attn_gate``,
``attn_core``, ``moe_route``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared``, ``head``, ``embed``.

**What is kept for the backward pass and what is built again** (8,192
positions, the benchmark's share; ``PERF.md`` section 6, PR 52 and PR 53).  Built
again: the convolutions' float32 taps and SiLU (the shared function's own
backward rule); all of ``kda_core`` from ``q~``, ``k~``, ``v``, the decay's and
``beta``'s products (:func:`_kda_core`, one ``jax.checkpoint``: on a TPU the
L2 norms, ``g``, ``beta`` and the rule's forward kernel run again in the
backward pass, which is where the 67 MB of states at every chunk's start live,
and the backward kernel builds the chunk's terms in fast memory; elsewhere the
composition's chunk scores, triangular systems and their solutions are built
again); the head norm and gate from ``o`` and ``z`` (:func:`_gate_norm`).  The
cell's step fits the chip with nothing else built again: 14.22 GB of its 16.9
(14.36 with the composition; keeping the kernel's states instead of running it
again reads 8.9 ms a step less and 0.3 GB more).
"""

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.delta_rule import gated_delta_rule
from bagua_tpu.models.decoder import (
    GroupedQueryAttention,
    Kernels,
    RMSNorm,
    SwiGLU,
    causal_conv_silu,
    matmul,
    next_token_loss_fn,
)
from bagua_tpu.models.embedding import embed
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "moe_intermediate_size",
    "rms_norm_eps", "tie_word_embeddings", "first_k_dense_replace", "use_rope", "gqa_layers",
    "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "routed_scaling_factor", "num_experts_per_tok",
    "linear_attn_config",
)
#: under the root of the L2 norms of ``q`` and ``k`` (the KDA layer's own; not in ``config.json``)
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """``linear_attn_config`` of ``config.json``."""

    short_conv_kernel_size: int = 4
    head_dim: int = 128
    num_heads: int = 64
    num_kv_heads: Optional[int] = None


def _range_of(held, total: int) -> Tuple[int, int]:
    return tuple(held) if held is not None else (0, total)


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: in ``config.json``; no layer reads it (``first_k_dense_replace`` 0)
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1280
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    first_k_dense_replace: int = 0
    use_rope: bool = False
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    num_experts_per_tok: int = 8
    linear_attn_config: Any = LinearAttnConfig()
    #: positions a chunk of the delta rule; not in ``config.json`` (the KDA layer's own)
    chunk_size: int = 64
    #: added to the sum of the chosen scores before the division (the family's code)
    router_eps: float = 1e-20
    #: ``(first, count)`` of the routed experts and of each mixer's heads whose
    #: kernels live here; None: all of them
    experts_held: Any = None
    heads_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config",
                               LinearAttnConfig(**self.linear_attn_config))
        layers = tuple(self.gqa_layers)
        object.__setattr__(self, "gqa_layers", layers)
        if any(not 0 <= n < self.num_hidden_layers for n in layers):
            raise ValueError(f"gqa_layers {layers} names a layer past {self.num_hidden_layers}")
        linear = self.linear_attn_config
        if linear.num_heads != self.num_attention_heads or linear.num_kv_heads is not None:
            raise NotImplementedError(
                "heads_held is one range for both mixers: built for as many KDA heads as query "
                "heads, keys and values on the query's heads")
        first, count = _range_of(self.experts_held, self.n_routed_experts)
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {(first, count)} is no range of {self.n_routed_experts}")
        group = self.num_attention_heads // self.num_key_value_heads
        first, count = self.held_heads
        if (first < 0 or count < 1 or first + count > self.num_attention_heads
                or self.num_attention_heads % self.num_key_value_heads
                or first % min(count, group) or (group % count if count < group else count % group)):
            raise ValueError(
                f"heads_held {(first, count)} is neither whole key-value heads' query heads "
                f"({group} each of {self.num_attention_heads}) nor an even part of one's")
        for key, want in (("tie_word_embeddings", False), ("first_k_dense_replace", 0),
                          ("use_rope", False), ("kda_use_full_proj", False),
                          ("n_shared_experts", 1)):
            if getattr(self, key) != want:
                raise NotImplementedError(f"{key}={getattr(self, key)!r}: built for {want!r}")

    @property
    def held(self) -> Tuple[int, int]:
        return _range_of(self.experts_held, self.n_routed_experts)

    @property
    def held_heads(self) -> Tuple[int, int]:
        return _range_of(self.heads_held, self.num_attention_heads)

    @property
    def key_value_heads_held(self) -> int:
        """How many key-value heads the held query heads read."""
        group = self.num_attention_heads // self.num_key_value_heads
        return max(1, self.held_heads[1] // group)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "SolarOpen2Config":
        """From a ``config.json`` of ``model_type`` ``solar_open2``."""
        return cls(**{**{k: config[k] for k in HF_KEYS if k in config}, **overrides})


def solar_open2_test_config(**overrides) -> SolarOpen2Config:
    """Every mechanism at a size for the CPU: a GQA layer and two KDA layers,
    four heads on two key-value heads, KDA heads of 8 over chunks of 16 (four
    sub-blocks of 4 positions: scores inside a sub-block and between two),
    top-3 of 8 experts and a shared one."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, intermediate_size=48, moe_intermediate_size=16,
        gqa_layers=(0,), n_routed_experts=8, num_experts_per_tok=3, chunk_size=16,
        linear_attn_config=LinearAttnConfig(4, 8, 4, None),
    )
    kwargs.update(overrides)
    return SolarOpen2Config(**kwargs)


# -- the KDA mixer ---------------------------------------------------------------


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of uniform(1, 16), the KDA layer's: decays that span short and
    long memory."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in [0.001,
    0.1]: the KDA layer's initial time steps."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(0.001), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def l2_normed(x, scale: float = 1.0):
    """``x / ||x||_2 * scale`` over the last dimension in float32, ``eps``
    under the root."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS) * scale)


@functools.partial(jax.checkpoint, static_argnums=(7, 8))
def _kda_core(q, k, v, decay, dt_bias, a_log, opening, neg_eigval: bool, chunk: int):
    """The layer's core from what the products and convolutions gave, ``q``,
    ``k``, ``v`` ``(batch, positions, heads, size)``, ``decay`` of that shape
    and ``opening (batch, positions, heads)`` float32: the L2 norms (``q`` with
    the score's scale), ``g``, ``beta`` and the delta rule; built again from
    its arguments in the backward pass."""
    dtype, size = v.dtype, q.shape[-1]
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(decay + dt_bias)
    beta = jax.nn.sigmoid(opening) * (2.0 if neg_eigval else 1.0)
    return gated_delta_rule(l2_normed(q, size ** -0.5).astype(dtype), l2_normed(k).astype(dtype),
                            v, g, beta, chunk)


@jax.checkpoint
def _gate_norm(o, z, scale, eps):
    """``RMSNorm_d(o) * scale * sigmoid(z)`` a head, ``(batch, positions,
    heads, size)``, in float32 and rounded once; built again from its inputs
    in the backward pass."""
    wide = o.astype(jnp.float32)
    mean = jnp.mean(jnp.square(wide), axis=-1, keepdims=True)
    return (wide * jax.lax.rsqrt(mean + eps) * scale * jax.nn.sigmoid(z)).astype(o.dtype)


class KdaMixer(Kernels):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, h):
        cfg, dtype = self.cfg, self.cfg.compute_dtype
        batch, t, hidden = h.shape
        linear = cfg.linear_attn_config
        heads, size = cfg.held_heads[1], linear.head_dim
        inner = heads * size
        by_head = (batch, t, heads, size)

        def narrow(name, x, columns):
            """A product whose result stays float32 from the product on."""
            return jnp.dot(x.astype(dtype), self.kernel(name, x.shape[-1], columns).astype(dtype),
                           preferred_element_type=jnp.float32)

        with model_scope("kda_proj"):
            q, k, v = (matmul(h, self.kernel(name + "_proj", hidden, inner), dtype)
                       for name in ("q", "k", "v"))
            decay = narrow("f_b_proj", matmul(h, self.kernel("f_a_proj", hidden, size), dtype), inner)
            opening = narrow("b_proj", h, heads)
            z = narrow("g_b_proj", matmul(h, self.kernel("g_a_proj", hidden, size), dtype), inner)
            z = z + self.param("g_bias", nn.initializers.zeros, (inner,), jnp.float32)
        with model_scope("kda_conv"):
            taps = nn.initializers.normal(linear.short_conv_kernel_size ** -0.5)
            q, k, v = (causal_conv_silu(x, self.param(
                name + "_conv", taps, (linear.short_conv_kernel_size, inner), jnp.float32))
                for name, x in (("q", q), ("k", k), ("v", v)))
        with model_scope("kda_core"):
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
            o = _kda_core(q.reshape(by_head), k.reshape(by_head), v.reshape(by_head),
                          decay.reshape(by_head), dt_bias.reshape(heads, size), a_log, opening,
                          cfg.kda_allow_neg_eigval, cfg.chunk_size)
        with model_scope("kda_gate_norm"):
            scale = self.param("o_norm", nn.initializers.ones, (size,), jnp.float32)
            y = _gate_norm(o, z.reshape(by_head), scale, cfg.rms_norm_eps)
        with model_scope("kda_proj"):
            return matmul(y.reshape(batch, t, inner), self.kernel("o_proj", inner, hidden), dtype)


# -- the expert layer ------------------------------------------------------------


class SparseExperts(Kernels):
    """The router over all routed experts, the held experts' part of the
    routed result, and the shared expert."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, t, hidden = u.shape
        experts, (first, count) = cfg.n_routed_experts, cfg.held
        width = cfg.moe_intermediate_size
        tokens = u.reshape(b * t, hidden)
        with model_scope("moe_route"):
            chosen, weights = sigmoid_topk_route(
                tokens, self.kernel("router", hidden, experts),
                self.kernel("correction_bias", experts), cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.router_eps)
        routed = dropless_experts(
            tokens, chosen, weights,
            self.kernel("experts_gate", count, hidden, width),
            self.kernel("experts_up", count, hidden, width),
            self.kernel("experts_down", count, width, hidden),
            held=(first, count), num_experts=experts)
        with model_scope("moe_shared"):
            shared = SwiGLU(width, cfg.compute_dtype, name="shared")(u)
        return routed.reshape(b, t, hidden) + shared


class SolarOpen2Block(nn.Module):
    cfg: SolarOpen2Config
    layer: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x)
        if self.layer in cfg.gqa_layers:
            x = x + GroupedQueryAttention(
                cfg.held_heads[1], cfg.key_value_heads_held, cfg.head_dim, cfg.compute_dtype,
                gate="column" if cfg.use_gqa_gate else False, name="attn")(h)
        else:
            x = x + KdaMixer(cfg, name="kda")(h)
        u = RMSNorm(cfg.rms_norm_eps, name="post_mixer_norm")(x)
        return x + SparseExperts(cfg, name="moe")(u)


class SolarOpen2Model(Kernels):
    """``ids (batch, positions)`` to float32 logits ``(batch, positions,
    vocab)`` through the output matrix."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, ids):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        x = embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dt)
        for n in range(cfg.num_hidden_layers):
            x = SolarOpen2Block(cfg, n, name=f"layer_{n}")(x)
        with model_scope("head"):
            h = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            return jnp.einsum("btm,mv->btv", h.astype(dt), head.astype(dt),
                              preferred_element_type=jnp.float32)


solar_open2_loss_fn = next_token_loss_fn
