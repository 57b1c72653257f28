"""BERT encoder family (BERT-Large is the reference's second headline
benchmark: 128-GPU finetune, ``README.md:50-53``).

TPU-native flax implementation with composable parallelism:

* **TP**: attention QKV is column-parallel (heads sharded over ``tp``), the
  output projection row-parallel; the FFN is a Column→Row pair — two forward
  allreduces per layer, Megatron-style.
* **SP (long context)**: the sequence dimension is sharded over ``sp`` and
  attention runs as ring attention (``bagua_tpu.parallel.ring_attention``);
  position embeddings are offset by the rank's global block start.
* **DP**: comes from the engine (batch sharded over the group axes).

``tp_size`` is static so parameter shapes are rank-local; axes are checked
at apply time.

**The fused query-key-value result stays where its product wrote it**
(``PERF.md`` section 6, PR 51).  A head of 64 columns fills half a 128-lane
tile, so the TPU compiler lays the scores' operands out by the 128 positions;
left alone it wrote the fused product's ``(batch, positions, 3 x hidden)``
result with the columns minor and copied it into that layout, and copied the
joined gradient back before the projection's two gradient products: 48 copies
of 25 MB a step in BERT-Large.  Where the local core runs at heads narrower
than a lane tile, on a TPU, :func:`_heads_apart` holds the product's result to
the layout its reader takes (``kernels/head_passes.py::held_to``: no pass, a
constraint on the compiler's choice) and writes the join of the three
gradients itself, under the same constraint; no arithmetic changes.  Every
other layer (ring attention, a ``kv_mask``, heads of whole lane tiles, another
backend) is written as it was.
"""

import dataclasses
import functools
from typing import Any, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.kernels.head_passes import held_to
from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.parallel.ring_attention import ring_attention, _block_attention_local
from bagua_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    ParallelMLP,
    RowParallelDense,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    # parallelism
    tp_size: int = 1
    tp_axis: Union[str, Tuple[str, ...]] = "tp"
    sp_axis: Union[str, Tuple[str, ...], None] = None  # ring attention when set
    compute_dtype: Any = jnp.float32
    #: rematerialize each layer's activations in the backward pass
    #: (jax.checkpoint) — trades FLOPs for HBM, the standard TPU memory lever
    remat: bool = False


def bert_large_config(**overrides) -> BertConfig:
    return BertConfig(**overrides)


def bert_base_config(**overrides) -> BertConfig:
    return BertConfig(
        hidden_size=768, num_layers=12, num_heads=12, intermediate_size=3072, **overrides
    )


def _sp_offset(cfg: BertConfig, t_local: int):
    """Global position offset of this rank's sequence block under SP."""
    if cfg.sp_axis is None:
        return 0
    try:
        from bagua_tpu.communication import rank_id

        return rank_id(
            (cfg.sp_axis,) if isinstance(cfg.sp_axis, str) else cfg.sp_axis
        ) * t_local
    except NameError:
        return 0


#: ``(batch, positions, columns)`` with the positions along the lanes
_POSITIONS_MINOR = (0, 2, 1)


def _apart(qkv, heads: int):
    """``q``, ``k``, ``v`` ``(batch, positions, heads, head size)`` out of the
    fused product's ``(batch, positions, 3 x heads x head size)``."""
    b, t, _ = qkv.shape
    qkv = qkv.reshape(b, t, 3, heads, -1)
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _heads_apart(qkv, heads: int):
    """:func:`_apart` with the product's result held to the layout the scores
    read it in.  Backward: the three gradients joined in the projection's
    column order, held to the same layout, which the projection's gradient
    products read as it stands (autodiff's join, the transpose of three
    slices, writes the axis of three outermost and is copied)."""
    return _apart(held_to(qkv, _POSITIONS_MINOR), heads)


def _heads_apart_fwd(qkv, heads):
    return _heads_apart(qkv, heads), None


def _heads_apart_bwd(heads, _, grads):
    b, t = grads[0].shape[:2]
    joined = jnp.concatenate([g.reshape(b, t, -1) for g in grads], axis=-1)
    return (held_to(joined, _POSITIONS_MINOR),)


_heads_apart.defvjp(_heads_apart_fwd, _heads_apart_bwd)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        b, t, _ = x.shape
        if cfg.num_heads % cfg.tp_size != 0:
            raise ValueError("num_heads must divide by tp_size")
        local_heads = cfg.num_heads // cfg.tp_size
        head_dim = cfg.hidden_size // cfg.num_heads

        # the local core at heads narrower than a lane tile (the module's text)
        held = (cfg.sp_axis is None and mask is None and head_dim % 128 != 0
                and jax.default_backend() == "tpu")
        with model_scope("attn_proj"):
            qkv = ColumnParallelDense(
                3 * cfg.hidden_size, cfg.tp_size, cfg.tp_axis, dtype=cfg.compute_dtype,
                name="qkv",
            )(x)
            q, k, v = (_heads_apart if held else _apart)(qkv, local_heads)

        with model_scope("attn_core"):
            if cfg.sp_axis is not None:
                ctx = ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=False, kv_mask=mask)
            else:
                ctx = _block_attention_local(q, k, v, causal=False, kv_mask=mask)
        with model_scope("attn_proj"):
            ctx = ctx.reshape(b, t, local_heads * head_dim)
            return RowParallelDense(
                cfg.hidden_size, cfg.tp_size, cfg.tp_axis, dtype=cfg.compute_dtype,
                name="out",
            )(ctx)


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        attn = BertSelfAttention(cfg, name="attention")(x, mask)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_attn")(x + attn)
        ffn = ParallelMLP(
            cfg.intermediate_size, cfg.hidden_size, cfg.tp_size, cfg.tp_axis,
            dtype=cfg.compute_dtype, name="mlp",
        )(x)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_ffn")(x + ffn)


class BertModel(nn.Module):
    """Encoder producing final hidden states ``(B, T_local, H)``."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        cfg = self.cfg
        b, t = input_ids.shape
        word = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="word_embeddings")(input_ids)
        pos_ids = jnp.arange(t)[None, :] + _sp_offset(cfg, t)
        pos = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, name="position_embeddings"
        )(pos_ids)
        x = word + pos
        if token_type_ids is not None:
            x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size, name="token_type_embeddings")(
                token_type_ids
            )
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_embed")(x)
        x = x.astype(cfg.compute_dtype)
        layer_cls = nn.remat(BertLayer) if cfg.remat else BertLayer
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, attention_mask)
        return x.astype(jnp.float32)


class BertForPreTraining(nn.Module):
    """Encoder + MLM head (untied decoder)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None):
        h = BertModel(self.cfg, name="bert")(input_ids, token_type_ids)
        h = nn.Dense(self.cfg.hidden_size, name="mlm_transform")(h)
        h = jax.nn.gelu(h)
        h = nn.LayerNorm(epsilon=self.cfg.layer_norm_eps, name="mlm_ln")(h)
        return nn.Dense(self.cfg.vocab_size, name="mlm_decoder")(h)


def mlm_loss_fn(model: BertForPreTraining):
    """Masked-LM cross entropy over all positions (synthetic-benchmark style)."""

    def loss_fn(params, batch):
        input_ids, labels = batch
        logits = model.apply({"params": params}, input_ids)
        return jnp.mean(softmax_cross_entropy(logits, labels))

    return loss_fn
