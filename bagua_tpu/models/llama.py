"""Llama-style causal decoder: RMSNorm, rotary position embeddings (RoPE),
SwiGLU MLP, and grouped-query attention (GQA).

Beyond the reference (its model zoo stops at the VGG/BERT example tier) —
included to show the parallel substrate carries contemporary decoder
architectures unchanged: the blocks compose the same Megatron TP pairing
(`parallel/tensor_parallel.py`), ring-attention SP with contiguous or zigzag
layouts (`parallel/ring_attention.py`), and the GPT model's SP position /
seam-masked LM loss machinery (`models/gpt.py`) — one TP allreduce per
attention block and per MLP, RoPE applied to each rank's *global* token
positions before the ring exchange.
"""

import dataclasses
from typing import Any, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.models.decoder import RMSNorm
from bagua_tpu.models.gpt import _sp_positions, lm_loss_fn  # noqa: F401  (re-exported)
from bagua_tpu.parallel.ring_attention import _block_attention_local, ring_attention
from bagua_tpu.parallel.tensor_parallel import ColumnParallelDense, RowParallelDense


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    #: < num_heads enables grouped-query attention; K/V heads are shared by
    #: ``num_heads // num_kv_heads`` query heads each
    num_kv_heads: int = 32
    intermediate_size: int = 11008
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tp_size: int = 1
    tp_axis: Union[str, Tuple[str, ...]] = "tp"
    sp_axis: Union[str, Tuple[str, ...], None] = None
    #: "contiguous" or "zigzag" (see GPTConfig.sp_layout)
    sp_layout: str = "contiguous"
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"hidden_size ({self.hidden_size}) must divide by num_heads "
                f"({self.num_heads})"
            )
        if (self.hidden_size // self.num_heads) % 2:
            raise ValueError(
                f"head_dim ({self.hidden_size // self.num_heads}) must be even "
                "(RoPE rotates half-dimension pairs)"
            )
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must divide by num_kv_heads "
                f"({self.num_kv_heads})"
            )
        for field, n in (("num_heads", self.num_heads), ("num_kv_heads", self.num_kv_heads)):
            if n % self.tp_size:
                raise ValueError(
                    f"{field} ({n}) must divide by tp_size ({self.tp_size})"
                )


def llama_7b_config(**overrides) -> LlamaConfig:
    """The classic 7B shape (32 layers x 4096 hidden, MHA)."""
    return LlamaConfig(**overrides)


def llama_test_config(**overrides) -> LlamaConfig:
    kwargs = dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
        intermediate_size=48, max_position_embeddings=64,
    )
    kwargs.update(overrides)
    return LlamaConfig(**kwargs)


def apply_rope(x, positions, theta: float):
    """Rotate interleaved feature pairs of ``x`` (b, t, h, d) by the angles of
    ``positions`` (t,).  Computed in f32, cast back to ``x.dtype``."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (t, d/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., ::2], xf[..., 1::2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, _ = x.shape
        head_dim = cfg.hidden_size // cfg.num_heads
        local_q = cfg.num_heads // cfg.tp_size
        local_kv = cfg.num_kv_heads // cfg.tp_size

        def proj(n_heads, name):
            return ColumnParallelDense(
                n_heads * head_dim, cfg.tp_size, cfg.tp_axis, use_bias=False,
                dtype=cfg.compute_dtype, name=name,
            )(x)

        q = proj(cfg.num_heads, "q").reshape(b, t, local_q, head_dim)
        k = proj(cfg.num_kv_heads, "k").reshape(b, t, local_kv, head_dim)
        v = proj(cfg.num_kv_heads, "v").reshape(b, t, local_kv, head_dim)

        # RoPE on the *global* positions of this rank's tokens — under SP the
        # K/V blocks carry their rotation with them around the ring.
        pos = _sp_positions(cfg, t)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

        if cfg.sp_axis is not None:
            # GQA rides the ring unrepeated: kv_groups expands the shared
            # K/V heads inside the per-block compute, so the ring hops carry
            # 1/group of the K/V bytes.
            ctx = ring_attention(
                q, k, v, axis_name=cfg.sp_axis, causal=True, layout=cfg.sp_layout,
                kv_groups=local_q // local_kv,
            )
        else:
            if local_q != local_kv:  # local path: expand before the oracle
                k = jnp.repeat(k, local_q // local_kv, axis=2)
                v = jnp.repeat(v, local_q // local_kv, axis=2)
            ctx = _block_attention_local(q, k, v, causal=True)
        return RowParallelDense(
            cfg.hidden_size, cfg.tp_size, cfg.tp_axis, use_bias=False,
            dtype=cfg.compute_dtype, name="out",
        )(ctx.reshape(b, t, local_q * head_dim))


class LlamaMLP(nn.Module):
    """SwiGLU: down(silu(gate(x)) * up(x)) — two column projections, one row
    projection, one TP allreduce total."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        col = lambda name: ColumnParallelDense(
            cfg.intermediate_size, cfg.tp_size, cfg.tp_axis, use_bias=False,
            dtype=cfg.compute_dtype, name=name,
        )
        h = jax.nn.silu(col("gate")(x)) * col("up")(x)
        return RowParallelDense(
            cfg.hidden_size, cfg.tp_size, cfg.tp_axis, use_bias=False,
            dtype=cfg.compute_dtype, name="down",
        )(h)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        x = x + LlamaAttention(self.cfg, name="attn")(
            RMSNorm(self.cfg.norm_eps, name="attn_norm")(x)
        )
        return x + LlamaMLP(self.cfg, name="mlp")(
            RMSNorm(self.cfg.norm_eps, name="mlp_norm")(x)
        )


class LlamaModel(nn.Module):
    """Causal LM: embed -> pre-norm blocks -> RMSNorm -> untied f32 LM head.
    Output: (b, t, vocab) logits."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        # RoPE itself is unbounded, but the config's trained context length
        # is still a real contract — enforce it against the *global* sequence
        # (sp axis size x local length, both static).
        try:
            from bagua_tpu.communication import axis_size

            axes = (cfg.sp_axis,) if isinstance(cfg.sp_axis, str) else cfg.sp_axis
            sp = axis_size(axes) if cfg.sp_axis is not None else 1
        except NameError:
            sp = 1
        t_global = sp * input_ids.shape[1]
        if t_global > cfg.max_position_embeddings:
            raise ValueError(
                f"global sequence length {t_global} exceeds the configured "
                f"max_position_embeddings ({cfg.max_position_embeddings})"
            )
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed")(input_ids)
        x = x.astype(cfg.compute_dtype)
        for i in range(cfg.num_layers):
            x = LlamaBlock(cfg, name=f"block_{i}")(x)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x.astype(jnp.float32))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


# ``lm_loss_fn`` (imported from models.gpt) works unchanged: it reads only
# ``model.cfg.sp_axis`` / ``sp_layout`` and ``model.apply``, including the
# zigzag seam masking and its degenerate-layout fallback.
llama_loss_fn = lm_loss_fn
