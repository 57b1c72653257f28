"""GPT-style causal decoder with optional ring-attention sequence parallelism
— the long-context demonstration model (causal ring attention over the ``sp``
axis lets context length scale with the number of chips)."""

import dataclasses
from typing import Any, Tuple, Union

import flax.linen as nn
import jax.numpy as jnp

from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.parallel.ring_attention import ring_attention, _block_attention_local
from bagua_tpu.parallel.tensor_parallel import ColumnParallelDense, ParallelMLP, RowParallelDense


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 2048
    tp_size: int = 1
    tp_axis: Union[str, Tuple[str, ...]] = "tp"
    sp_axis: Union[str, Tuple[str, ...], None] = None
    #: "contiguous" or "zigzag" — the balanced causal ring layout; feed
    #: token ids permuted with ``ring_attention.zigzag_order`` and the model
    #: assigns the matching global positions (see docs/parallelism.md)
    sp_layout: str = "contiguous"
    compute_dtype: Any = jnp.float32


def _zigzag_active(cfg: GPTConfig) -> bool:
    """Is the zigzag layout actually in effect (axis bound, >1 rank)?  With
    the ``sp`` axis unbound (single-device eval/debug outside shard_map) or of
    size 1, zigzag degenerates to the identity layout — positions, attention,
    AND the loss seam mask must all take the contiguous path together."""
    if cfg.sp_axis is None or cfg.sp_layout != "zigzag":
        return False
    try:
        from bagua_tpu.communication import axis_size

        axes = (cfg.sp_axis,) if isinstance(cfg.sp_axis, str) else cfg.sp_axis
        return axis_size(axes) > 1
    except NameError:
        return False


def _sp_positions(cfg: GPTConfig, t_local: int):
    """Global position ids of this rank's local tokens, shape (t_local,)."""
    if cfg.sp_axis is None:
        return jnp.arange(t_local)
    try:
        from bagua_tpu.communication import axis_size, rank_id

        axes = (cfg.sp_axis,) if isinstance(cfg.sp_axis, str) else cfg.sp_axis
        r = rank_id(axes)
        if _zigzag_active(cfg):
            if t_local % 2:
                # fail here, with the real constraint, rather than as an
                # opaque broadcast error at the position-embedding add
                raise ValueError(
                    f"zigzag sp layout needs an even local sequence length, "
                    f"got {t_local}"
                )
            sp = axis_size(axes)
            t2 = t_local // 2
            return jnp.concatenate([
                r * t2 + jnp.arange(t2),
                (2 * sp - 1 - r) * t2 + jnp.arange(t2),
            ])
        return r * t_local + jnp.arange(t_local)
    except NameError:
        return jnp.arange(t_local)


class GPTBlock(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, _ = x.shape
        local_heads = cfg.num_heads // cfg.tp_size
        head_dim = cfg.hidden_size // cfg.num_heads

        h = nn.LayerNorm(name="ln1")(x)
        qkv = ColumnParallelDense(
            3 * cfg.hidden_size, cfg.tp_size, cfg.tp_axis, dtype=cfg.compute_dtype, name="qkv"
        )(h).reshape(b, t, 3, local_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cfg.sp_axis is not None:
            ctx = ring_attention(
                q, k, v, axis_name=cfg.sp_axis, causal=True, layout=cfg.sp_layout
            )
        else:
            ctx = _block_attention_local(q, k, v, causal=True)
        attn = RowParallelDense(
            cfg.hidden_size, cfg.tp_size, cfg.tp_axis, dtype=cfg.compute_dtype, name="out"
        )(ctx.reshape(b, t, local_heads * head_dim))
        x = x + attn
        h = nn.LayerNorm(name="ln2")(x)
        return x + ParallelMLP(
            4 * cfg.hidden_size, cfg.hidden_size, cfg.tp_size, cfg.tp_axis,
            dtype=cfg.compute_dtype, name="mlp",
        )(h)


class GPTModel(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        b, t = input_ids.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="wte")(input_ids)
        pos = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size, name="wpe")(
            _sp_positions(cfg, t)[None, :]
        )
        x = (x + pos).astype(cfg.compute_dtype)
        for i in range(cfg.num_layers):
            x = GPTBlock(cfg, name=f"block_{i}")(x)
        x = nn.LayerNorm(name="ln_f")(x.astype(jnp.float32))
        wte = self.variables["params"]["wte"]["embedding"]
        return x @ wte.T  # tied LM head


def lm_loss_fn(model: GPTModel):
    """Next-token cross entropy (within the local block under SP).  With
    ``sp_layout="zigzag"`` the two local half-blocks are globally
    non-adjacent, so the mid-block seam pair (local ``t2-1 -> t2``) is a
    wrong prediction target — it is masked out of the mean."""
    cfg = model.cfg

    def loss_fn(params, batch):
        ids = batch
        logits = model.apply({"params": params}, ids)
        nll = softmax_cross_entropy(logits[:, :-1], ids[:, 1:])
        if _zigzag_active(cfg):
            t = ids.shape[1]
            if t < 4:
                # t == 2 would leave zero targets after the seam mask and
                # divide by zero (NaN loss) — fail with the real constraint.
                raise ValueError(
                    f"zigzag LM loss needs a local sequence length >= 4 "
                    f"(seam masking leaves no targets at {t})"
                )
            keep = jnp.arange(t - 1) != (t // 2 - 1)  # drop the seam pair
            return jnp.sum(nll * keep[None]) / (nll.shape[0] * (t - 2))
        return jnp.mean(nll)

    return loss_fn
