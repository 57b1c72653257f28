"""Ouro-2.6B (``model_type`` ``ouro``; the looped language models of ByteDance
Seed, "Scaling Latent Reasoning via Looped Language Models"): a causal decoder
whose whole stack of layers runs ``total_ut_steps`` times over its own output
with the *same* parameters, with the final norm and a one-column exit gate
after every pass, and the head on every pass's normed state.  Built from the
keys of the published ``config.json`` (:meth:`OuroConfig.from_hf`).

Layer ``n``, on the residual stream ``x`` (RMSNorm with a learned scale, no
bias, four norms a layer: the family's code norms the mixer's and the MLP's
*results* too):

* ``a = RMSNorm(x)``; ``q``, ``k``, ``v`` onto ``num_attention_heads`` heads
  of ``head_dim`` columns (as many key-value heads); the rotary embedding on
  all columns of ``q`` and ``k`` in the rotate-half pairing, positions ``0 ..
  T - 1`` in every pass; the causal core
  (:func:`~bagua_tpu.kernels.causal_attention.causal_attention`, on the chip
  splash's multi-head kernels); ``x += RMSNorm(ctx W_o)``.
* ``x += RMSNorm(SwiGLU(RMSNorm(x)))`` of ``intermediate_size``.

The model: ``h_0 = E[ids]`` (:func:`~bagua_tpu.models.embedding.embed`); for
``t = 1 .. total_ut_steps``: ``h_t = RMSNorm_f(Layers(h_{t-1}))``, the normed
state being what the next pass reads; ``logits_t = h_t W_head`` (a matrix of
its own); ``lambda_t = sigmoid(h_t . w_exit + b_exit)``.  The loss
(:func:`ouro_loss_fn`) is the family's first-stage objective: the passes'
next-token cross entropies weighted a position by the exit distribution
(:func:`exit_distribution`), less ``entropy_beta`` times that distribution's
entropy.

The passes are written out one after the other (a Python loop, not a
``lax.scan``), each under a ``bagua_model/pass=<t>`` scope, for two reasons.
A weight's gradient is the sum of its visits' and is complete when the
*first* pass's backward has run (the output matrix's when the last pass's
exit has, see below); written out, each bucket's exchange
(``bucket.py::wrap_params_for_overlap``) hangs on that sum where it completes,
inside the backward pass.  Rolled, the sum is the scan's carry, complete only
when the scan ends, and every exchange falls after the backward.  And each
operation of the capture belongs to one pass by its label, with nothing to
split by order.

**What the backward pass recomputes** is fixed here and is no option: nothing.
At the published widths and 8,192 positions one application of a layer leaves
about 0.63 GB for the backward pass if every array autodiff asks for is kept
(the stream three times, four norms' inputs, ``q``, ``k``, ``v``, the context,
three arrays of 5,632 columns, all bf16): sixteen applications are 10 GB beside
3.3 GB of float32 weights and gradients, and each exit's float32 logits are
1.61 GB, which cannot stand through the backward pass four times.  So:

* every exit takes the head's two gradient products in the *forward* pass, on
  the logits its forward product has just written (:func:`_exit_sum`).  It can,
  because of how the objective is built: an exit's cross entropies enter the
  loss as ``sum_rows w_t[row] CE_t[row]`` with ``w_t`` the exit distribution's
  share of pass ``t`` times the mean's row weight, and that share depends on
  the gates of passes ``1 .. t`` alone (:func:`exit_share`).  So the rows'
  weights are an *input* of the exit and its result is a scalar: the whole
  cotangent of the logits is known at the exit up to the one number that
  comes back from the loss, and the backward pass scales three kept arrays by
  it.  An exit keeps the normed state's gradient (32 MB), the output matrix's
  gradient as a partial sum in the compute dtype (0.20 GB in bf16; their sum
  over the passes is float32) and the rows' cross entropies: 0.93 GB for the
  four at most.  Where that number is the constant one (``value_and_grad`` of
  the loss as written, which is the engine's step) the compiler adds the four
  partial sums and the update right after the last exit, in the forward pass,
  and the backward pass never reads the output matrix: the compiled step
  holds 10.68 GB of temporaries where one that rebuilt the logits held 11.80.
  No array of logits outlives its exit, in either pass, and the head's
  product runs four times a step.  (Until PR 43 each exit's logits were built
  a second time in the backward pass: 4 x 1.65 TFLOP, 43.6 ms of a 576 ms
  step.)
* the layers keep what autodiff asks for; see ``PERF.md`` section 5 for what
  the compiled step holds and what the alternatives read on the chip.

``mfu_pct`` counts nothing recomputed; the summary's ``recompute`` class is
the time of what is, and this model has none.

The parts shared with the other decoder models are ``models/decoder.py``'s
(``RMSNorm``, ``Kernels``, ``SwiGLU``, the attention layer with ``rope_theta``).

Parameters are stored in float32; matrix products take ``compute_dtype``
operands and accumulate in float32; norms, the rotation, the gate, the exit
distribution, the logits and the loss are float32.  Each part of the forward
pass sits under a ``bagua_model/part=...`` scope (``exit_gate``: the gate's
product, the exit distribution and its entropy; ``head``: an exit's three
products, its cross entropies and their weighted sum).
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.models.decoder import GroupedQueryAttention, Kernels, RMSNorm, SwiGLU
from bagua_tpu.models.embedding import embed
from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.observability.annotations import model_scope, pass_scope

#: ``config.json`` keys the model is built from
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_act", "rms_norm_eps",
    "rope_theta", "rope_scaling", "use_sliding_window", "tie_word_embeddings", "total_ut_steps",
)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = ("full_attention",) * 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_scaling: Any = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4
    #: weight of the exit distribution's entropy in the loss; not in
    #: ``config.json`` (the family's first training stage)
    entropy_beta: float = 0.1
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) != {"full_attention"}):
            raise ValueError(
                f"layer_types {self.layer_types} is no 'full_attention' for each of "
                f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError(
                f"num_key_value_heads ({self.num_key_value_heads}) must divide "
                f"num_attention_heads ({self.num_attention_heads}), and head_dim "
                f"({self.head_dim}) be even")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps}: the stack runs at least once")
        for key, published in (("hidden_act", "silu"), ("rope_scaling", None),
                               ("use_sliding_window", False), ("tie_word_embeddings", False)):
            if getattr(self, key) != published:
                raise NotImplementedError(
                    f"{key} {getattr(self, key)!r}: the published model has {published!r}")

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "OuroConfig":
        """From a ``config.json`` of ``model_type`` ``ouro``."""
        return cls(**{k: config[k] for k in HF_KEYS if k in config}, **overrides)


def ouro_test_config(**overrides) -> OuroConfig:
    """Every mechanism at a size for the CPU: two layers run three times."""
    kwargs = dict(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=2,
        layer_types=("full_attention",) * 2, num_attention_heads=4, num_key_value_heads=4,
        head_dim=8, total_ut_steps=3,
    )
    kwargs.update(overrides)
    return OuroConfig(**kwargs)


class OuroBlock(nn.Module):
    cfg: OuroConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x)
        x = x + RMSNorm(cfg.rms_norm_eps, name="input_norm_2")(GroupedQueryAttention(
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.compute_dtype,
            rope_theta=cfg.rope_theta, name="attn")(h))
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(x)
        with model_scope("dense_mlp"):
            h = SwiGLU(cfg.intermediate_size, cfg.compute_dtype, name="mlp")(h)
        return x + RMSNorm(cfg.rms_norm_eps, name="post_attention_norm_2")(h)


def _logits(h, head, dtype):
    return jnp.einsum("btm,mv->btv", h.astype(dtype), head.astype(dtype),
                      preferred_element_type=jnp.float32)


@jax.checkpoint
def _exit_cross_entropy(h, head, targets):
    """One exit's head and loss: ``(batch, positions)`` float32 cross
    entropies of ``h W_head`` against ``targets``.  The logits are built again
    in the backward pass: what the exit keeps is ``h``."""
    return softmax_cross_entropy(_logits(h, head, h.dtype), targets)


@jax.custom_vjp
def _exit(h, head, targets):
    """``(h, cross entropies)``: a pass's normed state on its way to the next
    pass and its exit's :func:`_exit_cross_entropy`, for a model that hands
    ``ouro_loss_fn`` the entropies a position and lets it weight them
    (:class:`OuroModel` weights them at the exit, :func:`_exit_sum`, and
    rebuilds nothing).  Written as one function with a backward rule of its
    own for the *order* of the backward pass alone: every exit's logits can be
    built again as soon as the backward pass begins (they depend on ``h`` and
    the head, not on any cotangent), and left to itself the compiler does
    build all of them then and holds them (four arrays of 1.61 GB at the
    published sizes) until each is used.  The rule ties ``h`` to the cotangent
    that comes back from the later passes, so an exit's logits are built when
    the backward pass has reached its pass.
    The forward pass has the same tie the other way round: the next pass
    starts from an ``h`` that waits for this exit's cross entropies, or the
    four exits' reductions are fused into the loss's one operation at the end
    of the forward pass, which then reads four arrays of logits at once."""
    return jax.lax.optimization_barrier((h, _exit_cross_entropy(h, head, targets)))


def _exit_fwd(h, head, targets):
    return _exit(h, head, targets), (h, head, targets)


def _exit_bwd(kept, cotangents):
    (h, head, targets), (d_onward, d_entropies) = kept, cotangents
    h, d_onward = jax.lax.optimization_barrier((h, d_onward))
    # the primal half of this vjp is dead code; the checkpoint builds the logits once, below
    _, vjp = jax.vjp(lambda h, head: _exit_cross_entropy(h, head, targets), h, head)
    d_h, d_head = vjp(d_entropies)
    return d_onward + d_h, d_head, None


_exit.defvjp(_exit_fwd, _exit_bwd)


@jax.custom_vjp
def _exit_sum(h, head, targets, weights):
    """``(h, sum_rows weights * CE)``: a pass's normed state on its way to the
    next pass and its exit's cross entropies of ``h W_head`` against
    ``targets``, weighted a row by ``weights (batch, positions)`` float32 and
    summed: what the exit adds to the loss, a scalar.  The backward rule does
    no product: differentiated, the forward pass takes the head's two gradient
    products on the logits it has just built, with ``weights`` as the cross
    entropies' cotangent, and keeps their results (see the module's
    docstring).  ``h`` goes through for the tie :func:`_exit` has: the next
    pass starts from an ``h`` that waits for this exit."""
    entropies = softmax_cross_entropy(_logits(h, head, h.dtype), targets)
    return jax.lax.optimization_barrier((h, jnp.sum(weights * entropies)))


def _exit_sum_fwd(h, head, targets, weights):
    dt = h.dtype
    # autodiff's own rule for the same composition, run here; the operands are the products'
    # (the compute dtype), so a pass's gradient of the output matrix is a partial sum in it
    entropies, pull = jax.vjp(
        lambda h, head: softmax_cross_entropy(_logits(h, head, dt), targets), h, head.astype(dt))
    d_h, d_head = pull(weights)
    # the next pass waits for the two products too: nothing may put them off while the logits stand
    h, total, kept = jax.lax.optimization_barrier(
        (h, jnp.sum(weights * entropies), (d_h, d_head, entropies)))
    return (h, total), kept


def _exit_sum_bwd(kept, cotangents):
    (d_h, d_head, entropies), (d_onward, g) = kept, cotangents
    return (d_onward + (g * d_h).astype(d_h.dtype), g * d_head.astype(jnp.float32), None,
            g * entropies)


_exit_sum.defvjp(_exit_sum_fwd, _exit_sum_bwd)


class OuroModel(Kernels):
    """``ids (batch, positions)`` to the passes' exits, stacked over the
    passes: ``(logits (passes, batch, positions, vocab), gate logits (passes,
    batch, positions))``, float32; given ``targets (batch, positions)`` the
    first is what each exit adds to the loss, ``(passes,)``: its cross
    entropies against them weighted a position by the exit distribution's
    share of the pass and by :func:`mean_weights`, summed
    (:func:`_exit_sum`), and no array of logits outlives its exit."""

    cfg: OuroConfig

    @nn.compact
    def __call__(self, ids, targets=None):
        cfg, dt = self.cfg, self.cfg.compute_dtype
        x = embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dt)
        layers = [OuroBlock(cfg, name=f"layer_{n}") for n in range(cfg.num_hidden_layers)]
        final_norm = RMSNorm(cfg.rms_norm_eps, name="final_norm")
        head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
        w_exit = self.kernel("exit_gate", cfg.hidden_size)
        b_exit = self.param("exit_gate_bias", nn.initializers.zeros, (), jnp.float32)
        exits, gates = [], []
        # of a position's mass, before the first exit; and the mean over the positions as weights
        left, weights = jnp.ones(ids.shape, jnp.float32), mean_weights(ids.shape)
        for t in range(1, cfg.total_ut_steps + 1):
            with pass_scope(t):
                for layer in layers:
                    x = layer(x)
                x = final_norm(x)  # the normed state is what the next pass reads
                with model_scope("exit_gate"):
                    gates.append(jnp.einsum("btm,m->bt", x.astype(jnp.float32), w_exit,
                                            precision=jax.lax.Precision.HIGHEST) + b_exit)
                    share, left = exit_share(gates[-1], left, last=t == cfg.total_ut_steps)
                with model_scope("head"):
                    if targets is None:
                        at_exit = _logits(x, head, dt)
                    else:
                        x, at_exit = _exit_sum(x, head, targets, share * weights)
                exits.append(at_exit)
        return jnp.stack(exits), jnp.stack(gates)


def exit_share(gate_logit, left, last):
    """One pass of :func:`exit_distribution`, from the gates seen so far:
    ``(the pass's share, what it leaves)`` of the mass ``left`` that the passes
    before it left a position; the last pass takes all of it whatever its gate
    says."""
    if last:
        return left, jnp.zeros_like(left)
    gate = jax.nn.sigmoid(gate_logit)
    return gate * left, (1.0 - gate) * left


def exit_distribution(gate_logits):
    """``(passes, ...)`` gate logits to the exit distribution over the passes,
    same shape: pass ``t`` takes ``sigmoid(gate_t)`` of what the passes before
    it left, the last pass all that is left, so the shares sum to one."""
    gates = jax.nn.sigmoid(gate_logits[:-1])
    left = jnp.concatenate([jnp.ones_like(gate_logits[:1]), jnp.cumprod(1.0 - gates, axis=0)])
    return jnp.concatenate([gates, jnp.ones_like(gate_logits[:1])]) * left


def distribution_entropy(p):
    """``-sum_t p_t log p_t`` over the first axis, a share of exactly zero
    adding nothing, to the value or to the gradient."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)


def mean_weights(shape):
    """The mean over each sequence's ``positions - 1`` targets as a weight a
    row, ``(batch, positions)`` float32: ``1 / (batch * (positions - 1))``, and
    0 at the last position, which has no following token."""
    batch, positions = shape
    has_target = jnp.arange(positions) < positions - 1
    return jnp.broadcast_to(jnp.where(has_target, 1.0 / (batch * (positions - 1)), 0.0), shape)


def ouro_loss_fn(model: OuroModel):
    """The family's first-stage objective, mean over each sequence's
    ``positions - 1`` targets: ``sum_t p_t CE_t - entropy_beta H(p)``, ``CE_t``
    the next-token cross entropy at exit ``t`` and ``p`` the exit distribution
    a position; gradients flow into ``p`` as into the logits.  ``batch`` is
    the ids alone.  Given targets the model returns either what each exit adds
    to the mean, ``(passes,)`` (:class:`OuroModel`), or the exits' cross
    entropies a position, ``(passes, batch, positions)``, which are weighted
    here."""

    def loss_fn(params, batch):
        at_exits, gate_logits = model.apply({"params": params}, batch, jnp.roll(batch, -1, axis=1))
        with model_scope("exit_gate"):
            p, weights = exit_distribution(gate_logits), mean_weights(batch.shape)
            if at_exits.ndim == 3:
                at_exits = jnp.sum(weights * p * at_exits, axis=(1, 2))
            return jnp.sum(at_exits) - model.cfg.entropy_beta * jnp.sum(
                weights * distribution_entropy(p))

    return loss_fn
