"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` ``nemotron_h``): a causal
decoder whose blocks are each *one* part alone, a Mamba-2 mixer, an attention
layer, an expert layer or a dense MLP, by the letter the block has in
``hybrid_override_pattern`` (``M``, ``*``, ``E``, ``-``).  Built from the keys
of the published ``config.json`` (:meth:`NemotronHConfig.from_hf`).

The residual stream starts as the tokens' rows of the embedding table in
``compute_dtype`` (:func:`~bagua_tpu.models.embedding.embed`).  Every block is
``x <- x + f(RMSNorm(x))``: one norm with a learned scale, one addition, ``f``
by the letter; no bias anywhere but the convolution's.  After the last block
a final norm and an output matrix of its own.  On ``a = RMSNorm(x)``:

* ``M``, the Mamba-2 mixer (``d_inner = mamba_num_heads x mamba_head_dim``,
  ``G = n_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = a W_in`` of
  widths ``d_inner``, ``d_inner + 2 G N`` and one a head; ``xBC <-
  silu(conv(xBC) + b)``, depthwise and causal over ``conv_kernel`` taps
  (:func:`~bagua_tpu.models.decoder.causal_conv_silu`: ``y_t = sum_i w_i xBC_{t - (taps - 1) + i}``,
  zeros before the start), split into ``x`` (heads of ``mamba_head_dim``) and
  ``B``, ``C`` (``G`` groups of ``N``); ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; for head ``h`` of group ``g`` the state ``S`` (head size x
  ``N``, from zero) ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g,t}^T``, ``y_t =
  S_t C_{g,t} + D_h x_t`` (:func:`~bagua_tpu.kernels.ssd_scan.ssd_scan`, in
  chunks of ``chunk_size``); ``y <- RMSNorm_group(y * silu(z)) * w``: the gate
  first, then the norm over each *group's* ``d_inner / G`` columns; ``f = y
  W_out``.
* ``*``, attention: ``q`` onto ``num_attention_heads`` heads and ``k``, ``v``
  onto ``num_key_value_heads`` heads of ``head_dim``, **no positional
  embedding** (the state-space layers carry position), each key-value head
  serving ``heads / kv heads`` query heads under the causal mask
  (:func:`~bagua_tpu.kernels.causal_attention.causal_attention`), ``W_o``.
* ``E``, the latent expert layer: the router reads the **hidden** state,
  :func:`~bagua_tpu.parallel.moe.dropless.sigmoid_topk_route` over all
  ``n_routed_experts`` (``num_experts_per_tok`` of largest ``sigmoid + b``,
  weights normalised over the chosen and times ``routed_scaling_factor``);
  ``l = a W_lat_in`` takes the tokens to ``moe_latent_size``, the chosen
  experts work *there*, ``E_i(l) = W_down,i relu(W_up,i l)^2`` (two products,
  no gate; :func:`~bagua_tpu.parallel.moe.dropless.dropless_experts` with
  ``gate=None``), and ``W_lat_out`` takes their weighted sum back: what crosses
  the row buffer (and, in a deployment, the exchange) is ``moe_latent_size``
  wide, a quarter of the hidden size.  The shared expert works on the hidden
  state at the hidden width, ``W_sd relu(W_su a)^2``; ``f = shared + routed``.
* ``-``, a dense MLP of ``intermediate_size``, ``W_d relu(W_u a)^2`` (in the
  family; not in this model's pattern).

The parts shared with the other decoder models are ``models/decoder.py``'s.
:class:`Attention` is this file's own, the one copy beside
``decoder.GroupedQueryAttention``: it *divides* ``q`` by ``sqrt(head size)``
where that multiplies by the reciprocal (another program by an ulp), and reads
its head counts from the ranges this chip holds.

**What this chip holds.**  Three ranges, ``(first, count)`` each, ``None`` for
all: ``experts_held`` of the routed experts (the router keeps its width, its
choices and its normalisation; the terms of the experts held elsewhere are
left out), ``mamba_heads_held`` of the mixer's heads in whole ``B``/``C``
groups (the share's ``W_in`` columns, convolution channels, ``dt_bias``,
``A_log``, ``D``, norm scale and ``W_out`` rows; the gated norm runs over a
group's columns, so a share of whole groups computes it alone), and
``attention_heads_held`` of the query heads with the key-value heads they
read.  Each mixer's result is then this chip's part of the sum over the heads
that ``W_out`` (``W_o``) takes, and the shares of all chips add up to the
whole (``tests/test_nemotron_h.py``).  No stand-in for the other chips.

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, ``dt`` (its columns of ``W_in`` are
a product of their own with a float32 result), the decays, the state, the
router, the logits and the loss are float32.  Each part of the forward pass
sits under a ``bagua_model/part=...`` scope: ``ssm_proj`` (``W_in``,
``W_out``), ``ssm_conv`` (taps, bias, SiLU), ``ssm_core`` (the scan, the ``D``
term, the gate and the group norm), ``moe_latent`` (the two latent
projections), ``moe_route`` / ``moe_dispatch`` / ``moe_experts`` /
``moe_combine`` / ``moe_shared``, ``attn_proj`` / ``attn_core``, ``dense_mlp``,
``head``, ``embed``.

**What is kept for the backward pass and what is built again** (8,192
positions, the benchmark's share; each decided by a plain SGD step's time of
one such layer on the chip beside the bytes the step compiled for the v5e
holds: ``PERF.md`` section 6, PR 45 and PR 46).  Built again: the
convolution's float32 taps and SiLU from ``xBC`` (its hand-written backward,
as ``lfm2_moe.gated_short_conv`` has one), the ``D`` term, gate and group norm
from the scan's result, ``x`` and ``z`` (:func:`_skip_gate_norm`: kept they
cost 157 MB a layer *and* 0.69 ms, 37.22 ms a mixer layer's step against
36.53), and, on a TPU, a chunk's decays and scores inside the scan's backward
kernel, in fast memory (``kernels/ssd_scan.py``: the forward kernel keeps the
state at each chunk's start, 33.6 MB a layer, and nothing else of its own;
elsewhere the plain form keeps what autodiff keeps).  Kept: the routed
experts' squared ReLU beside the up product's result (232 MB a layer at the
buffer's 65,536 rows of 2,688 columns, 1.95 ms faster than building it again:
59.60 ms an expert layer's step against 61.56), and every product's operands.
The cell's step holds 10.95 GiB of the chip's 15.75 with all of that.
"""

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.causal_attention import causal_attention
from bagua_tpu.kernels.ssd_scan import ssd_scan
from bagua_tpu.models.decoder import (
    HEADS_MAJOR, Kernels, RMSNorm, causal_conv_silu, matmul, next_token_loss_fn, product)
from bagua_tpu.models.embedding import embed
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "intermediate_size", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "use_conv_bias", "mamba_hidden_act", "time_step_min",
    "time_step_max", "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "n_group", "topk_group", "mlp_hidden_act", "layer_norm_epsilon",
    "tie_word_embeddings", "num_nextn_predict_layers",
)
KINDS = "ME*-"
#: the published pattern: 40 mixers, 40 expert layers, attention in eight places
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEM*EMEMEMEME")


def relu2(v):
    """``relu(v) ** 2``, the family's ``relu2``."""
    return jnp.square(jax.nn.relu(v))


def _range_of(held, total: int) -> Tuple[int, int]:
    return tuple(held) if held is not None else (0, total)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    intermediate_size: int = 2688
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_hidden_act: str = "silu"
    #: the range the initial ``dt`` is drawn from; they shape ``dt_bias`` alone
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    #: added to the sum of the chosen scores before the division; not in
    #: ``config.json`` (the family's code: 1e-20)
    router_eps: float = 1e-20
    #: ``(first, count)`` of the routed experts, of the mixer's heads and of
    #: the query heads whose kernels live here; None: all of them
    experts_held: Any = None
    mamba_heads_held: Any = None
    attention_heads_held: Any = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} is no one of {KINDS!r} for each of "
                f"{self.num_hidden_layers} layers")
        for name, total in (("experts", self.n_routed_experts), ("mamba_heads", self.mamba_num_heads),
                            ("attention_heads", self.num_attention_heads)):
            first, count = _range_of(getattr(self, name + "_held"), total)
            if first < 0 or count < 1 or first + count > total:
                raise ValueError(f"{name}_held {(first, count)} is no range of {total}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} heads do not divide into {self.n_groups} groups")
        per = self.mamba_num_heads // self.n_groups
        if any(n % per for n in self.mamba_held):
            raise ValueError(
                f"mamba_heads_held {self.mamba_held} is no whole number of groups of {per} heads")
        group = self.num_attention_heads // self.num_key_value_heads
        first, count = self.attention_held
        if self.num_attention_heads % self.num_key_value_heads or first % min(count, group) or (
                group % count if count < group else count % group):
            raise ValueError(
                f"attention_heads_held {(first, count)} is neither whole key-value heads' query "
                f"heads ({group} each) nor an even part of one's")
        for key, want in (("n_group", 1), ("topk_group", 1), ("n_shared_experts", 1),
                          ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                          ("tie_word_embeddings", False), ("num_nextn_predict_layers", 0)):
            if getattr(self, key) != want:
                raise NotImplementedError(f"{key}={getattr(self, key)!r}: built for {want!r}")

    @property
    def held(self) -> Tuple[int, int]:
        return _range_of(self.experts_held, self.n_routed_experts)

    @property
    def mamba_held(self) -> Tuple[int, int]:
        return _range_of(self.mamba_heads_held, self.mamba_num_heads)

    @property
    def attention_held(self) -> Tuple[int, int]:
        return _range_of(self.attention_heads_held, self.num_attention_heads)

    @property
    def key_value_heads_held(self) -> int:
        """How many key-value heads the held query heads read."""
        group = self.num_attention_heads // self.num_key_value_heads
        return max(1, self.attention_held[1] // group)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "NemotronHConfig":
        """From a ``config.json`` of ``model_type`` ``nemotron_h``."""
        return cls(**{**{k: config[k] for k in HF_KEYS if k in config}, **overrides})


def nemotron_h_test_config(**overrides) -> NemotronHConfig:
    """Every mechanism at a size for the CPU: the four kinds of block, four
    mixer heads in two groups over several chunks, two query heads a
    key-value head, top-5 of 16 experts in a latent width below the hidden."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=4, hybrid_override_pattern="ME*-",
        intermediate_size=48, mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=16, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        n_routed_experts=16, num_experts_per_tok=5, moe_intermediate_size=24, moe_latent_size=16,
        moe_shared_expert_intermediate_size=40,
    )
    kwargs.update(overrides)
    return NemotronHConfig(**kwargs)


# -- the Mamba-2 mixer ----------------------------------------------------------


@jax.checkpoint
def _skip_gate_norm(y, x, z, skip, scale, eps):
    """``RMSNorm_group((y + D x) * silu(z)) * w`` over each group's columns,
    ``(batch, positions, groups, heads a group x head size)`` each with
    ``skip`` and ``scale`` a column's, in float32 and rounded once; built again
    from its inputs in the backward pass.  A group's columns are the last
    dimension, whole: with a head's 64 last the compiler lays the positions
    along the lanes, and every array between this and the scan's kernels,
    which take the columns there, is copied across (``PERF.md`` section 6,
    PR 46)."""
    f32 = jnp.float32
    gated = (y.astype(f32) + skip * x.astype(f32)) * jax.nn.silu(z.astype(f32))
    mean = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    return (gated * jax.lax.rsqrt(mean + eps) * scale).astype(y.dtype)


def _dt_bias_init(low: float, high: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in ``[low,
    high]``: the family's initial time steps."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(low), math.log(high)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of uniform(1, 16): decays that span short and long memory."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(Kernels):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, a):
        cfg, dtype = self.cfg, self.cfg.compute_dtype
        batch, t, hidden = a.shape
        heads, size, state = cfg.mamba_held[1], cfg.mamba_head_dim, cfg.ssm_state_size
        per = cfg.mamba_num_heads // cfg.n_groups
        groups = heads // per
        inner, channels = heads * size, heads * size + 2 * groups * state
        with model_scope("ssm_proj"):
            w_in = self.kernel("in_proj", hidden, inner + channels + heads)
            z_xbc = matmul(a, w_in[:, :inner + channels], dtype)
            # the time steps stay float32 from the product on
            dt = jnp.dot(a.astype(dtype), w_in[:, inner + channels:].astype(dtype),
                         preferred_element_type=jnp.float32)
        with model_scope("ssm_conv"):
            taps = self.param("conv_taps", nn.initializers.normal(cfg.conv_kernel ** -0.5),
                              (cfg.conv_kernel, channels), jnp.float32)
            bias = (self.param("conv_bias", nn.initializers.zeros, (channels,), jnp.float32)
                    if cfg.use_conv_bias else jnp.zeros((channels,), jnp.float32))
            xbc = causal_conv_silu(z_xbc[..., inner:], taps, bias)
        with model_scope("ssm_core"):
            dt_bias = self.param(
                "dt_bias", _dt_bias_init(cfg.time_step_min, cfg.time_step_max), (heads,), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            scale = self.param("norm_scale", nn.initializers.ones, (inner,), jnp.float32)
            x = xbc[..., :inner].reshape(batch, t, heads, size)
            b, c = (xbc[..., inner + n * groups * state:inner + (n + 1) * groups * state].reshape(
                batch, t, groups, state) for n in (0, 1))
            y = ssd_scan(x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), b, c, cfg.chunk_size)
            by_group = (batch, t, groups, per * size)
            y = _skip_gate_norm(
                y.reshape(by_group), x.reshape(by_group), z_xbc[..., :inner].reshape(by_group),
                jnp.repeat(skip, size).reshape(groups, per * size),
                scale.reshape(groups, per * size), cfg.layer_norm_epsilon)
        with model_scope("ssm_proj"):
            return matmul(y.reshape(batch, t, inner), self.kernel("out_proj", inner, hidden), dtype)


# -- attention without positions ----------------------------------------------


class Attention(Kernels):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, a):
        cfg, dtype = self.cfg, self.cfg.compute_dtype
        hidden, size = a.shape[-1], cfg.head_dim
        heads, kv_heads = cfg.attention_held[1], cfg.key_value_heads_held
        with model_scope("attn_proj"):
            def heads_of(name, count):
                return self.kernel(name + "_proj", hidden, count * size).reshape(hidden, count, size)

            # q carries 1 / sqrt(head size) from the pass that rounds it
            q = (jnp.einsum(HEADS_MAJOR, a.astype(dtype), heads_of("q", heads).astype(dtype),
                            preferred_element_type=jnp.float32) / math.sqrt(size)).astype(dtype)
            k = product(HEADS_MAJOR, a, heads_of("k", kv_heads), dtype)
            v = product(HEADS_MAJOR, a, heads_of("v", kv_heads), dtype)
            out = self.kernel("out_proj", heads * size, hidden).reshape(heads, size, hidden)
        with model_scope("attn_core"):
            ctx = causal_attention(q, k, v, 1.0)
        with model_scope("attn_proj"):
            return product("bhtd,hdm->btm", ctx, out, dtype)


# -- the expert layer and the dense MLP ----------------------------------------


class SquaredReluMLP(Kernels):
    """``W_down relu(W_up a)^2``: two products, no gate."""

    width: int
    dtype: Any

    @nn.compact
    def __call__(self, a):
        hidden = a.shape[-1]
        raised = relu2(matmul(a, self.kernel("up", hidden, self.width), self.dtype))
        return matmul(raised, self.kernel("down", self.width, hidden), self.dtype)


class LatentExperts(Kernels):
    """The router over all routed experts on the hidden state, the held
    experts' part of the routed result in the latent width between its two
    projections, and the shared expert at the hidden width."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, a):
        cfg, dtype = self.cfg, self.cfg.compute_dtype
        batch, t, hidden = a.shape
        experts, (first, count) = cfg.n_routed_experts, cfg.held
        latent, width = cfg.moe_latent_size, cfg.moe_intermediate_size
        tokens = a.reshape(batch * t, hidden)
        with model_scope("moe_route"):
            chosen, weights = sigmoid_topk_route(
                tokens, self.kernel("router", hidden, experts),
                self.kernel("correction_bias", experts), cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.router_eps)
        with model_scope("moe_latent"):
            lowered = matmul(tokens, self.kernel("latent_in", hidden, latent), dtype)
        routed = dropless_experts(
            lowered, chosen, weights, None,
            self.kernel("experts_up", count, latent, width),
            self.kernel("experts_down", count, width, latent),
            held=(first, count), num_experts=experts, activation=relu2)
        with model_scope("moe_latent"):
            routed = matmul(routed, self.kernel("latent_out", latent, hidden), dtype)
        with model_scope("moe_shared"):
            shared = SquaredReluMLP(cfg.moe_shared_expert_intermediate_size, dtype, name="shared")(a)
        return shared + routed.reshape(batch, t, hidden)


class NemotronHBlock(nn.Module):
    """``x + f(RMSNorm(x))``, ``f`` by ``kind``."""

    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        a = RMSNorm(cfg.layer_norm_epsilon, name="norm")(x)
        if self.kind == "M":
            return x + Mamba2Mixer(cfg, name="mixer")(a)
        if self.kind == "*":
            return x + Attention(cfg, name="attn")(a)
        if self.kind == "E":
            return x + LatentExperts(cfg, name="moe")(a)
        with model_scope("dense_mlp"):
            return x + SquaredReluMLP(cfg.intermediate_size, cfg.compute_dtype, name="mlp")(a)


class NemotronHModel(Kernels):
    """``ids (batch, positions)`` to float32 logits ``(batch, positions,
    vocab)`` through the output matrix."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, ids):
        cfg, dtype = self.cfg, self.cfg.compute_dtype
        x = embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dtype)
        for n, kind in enumerate(cfg.hybrid_override_pattern):
            x = NemotronHBlock(cfg, kind, name=f"layer_{n}")(x)
        with model_scope("head"):
            h = RMSNorm(cfg.layer_norm_epsilon, name="final_norm")(x)
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            return jnp.einsum("btm,mv->btv", h.astype(dtype), head.astype(dtype),
                              preferred_element_type=jnp.float32)


nemotron_h_loss_fn = next_token_loss_fn
