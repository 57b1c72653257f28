"""ResNet family (ResNet-50 is a BASELINE.json config: decentralized SGD).

Standard bottleneck ResNet in flax, NHWC, optional bfloat16 compute, and
optional cross-replica SyncBatchNorm (``bagua_tpu.contrib.sync_batchnorm``)
so statistics match large-batch multi-chip training.
"""

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from bagua_tpu.contrib.sync_batchnorm import SyncBatchNorm
from bagua_tpu.models.losses import softmax_cross_entropy


class BottleneckBlock(nn.Module):
    features: int
    strides: int = 1
    compute_dtype: Any = jnp.float32
    sync_bn: bool = False

    def _norm(self, name):
        if self.sync_bn:
            return SyncBatchNorm(name=name)
        return nn.BatchNorm(use_running_average=False, momentum=0.9, name=name)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.features, (1, 1), dtype=self.compute_dtype, use_bias=False)(x)
        y = jax.nn.relu(self._norm("bn1")(y))
        y = nn.Conv(
            self.features, (3, 3), strides=(self.strides, self.strides),
            padding=1, dtype=self.compute_dtype, use_bias=False,
        )(y)
        y = jax.nn.relu(self._norm("bn2")(y))
        y = nn.Conv(self.features * 4, (1, 1), dtype=self.compute_dtype, use_bias=False)(y)
        y = self._norm("bn3")(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.features * 4, (1, 1), strides=(self.strides, self.strides),
                dtype=self.compute_dtype, use_bias=False, name="proj",
            )(residual)
            residual = self._norm("bn_proj")(residual)
        return jax.nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    compute_dtype: Any = jnp.float32
    sync_bn: bool = False

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.compute_dtype)
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=3, use_bias=False,
                    dtype=self.compute_dtype)(x)
        if self.sync_bn:
            x = SyncBatchNorm(name="bn_init")(x)
        else:
            x = nn.BatchNorm(use_running_average=False, momentum=0.9, name="bn_init")(x)
        x = jax.nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, n_blocks in enumerate(self.stage_sizes):
            for b in range(n_blocks):
                strides = 2 if i > 0 and b == 0 else 1
                x = BottleneckBlock(
                    64 * 2 ** i, strides=strides,
                    compute_dtype=self.compute_dtype, sync_bn=self.sync_bn,
                    name=f"stage{i}_block{b}",
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes)(x.astype(jnp.float32))


def resnet50(num_classes: int = 1000, compute_dtype=jnp.float32, sync_bn: bool = False) -> ResNet:
    return ResNet([3, 4, 6, 3], num_classes, compute_dtype, sync_bn)


def init_resnet50(key, image_size: int = 224, num_classes: int = 1000, compute_dtype=jnp.float32, sync_bn=False):
    model = resnet50(num_classes, compute_dtype, sync_bn)
    variables = model.init(key, jnp.zeros((1, image_size, image_size, 3), jnp.float32))
    return model, variables


def resnet_loss_fn(model: ResNet):
    """Cross-entropy.  The DDP params tree holds both ``params`` and
    ``batch_stats``; pass ``dp_filter=lambda n: "batch_stats" not in n`` to
    the engine so the (gradient-free) BN statistics are neither bucketed nor
    allreduced.  Stats updates inside the loss are dropped (deterministic
    benchmark mode, matching the reference's synthetic benchmark)."""

    def loss_fn(params, batch):
        x, y = batch
        logits, _ = model.apply(
            {"params": params["params"], "batch_stats": params["batch_stats"]},
            x, mutable=["batch_stats"],
        )
        return jnp.mean(softmax_cross_entropy(logits, y))

    return loss_fn
