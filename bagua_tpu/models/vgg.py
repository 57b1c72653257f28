"""VGG16 — the reference's headline benchmark model.

The reference benchmarks VGG16 with ``examples/benchmark/synthetic_benchmark.py``
(batch 32/GPU, CI thresholds in ``.buildkite/scripts/benchmark_master.sh:81-83``).
Implemented in flax.linen, NHWC (TPU-native layout), with an option to run the
conv/matmul compute in bfloat16 (MXU-friendly) while keeping parameters and
the loss in float32.
"""

from typing import Any, Sequence, Union

import flax.linen as nn
import jax.numpy as jnp

from bagua_tpu.models.losses import softmax_cross_entropy

# 'M' = 2x2 max pool; ints = conv output channels (VGG16 = config D)
VGG16_CFG: Sequence[Union[str, int]] = (
    64, 64, "M",
    128, 128, "M",
    256, 256, 256, "M",
    512, 512, 512, "M",
    512, 512, 512, "M",
)


class VGG(nn.Module):
    num_classes: int = 1000
    cfg: Sequence[Union[str, int]] = VGG16_CFG
    compute_dtype: Any = jnp.float32
    classifier_width: int = 4096

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.compute_dtype)
        for v in self.cfg:
            if v == "M":
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
            else:
                x = nn.Conv(int(v), (3, 3), padding=1, dtype=self.compute_dtype)(x)
                x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.classifier_width, dtype=self.compute_dtype)(x)
        x = nn.relu(x)
        x = nn.Dense(self.classifier_width, dtype=self.compute_dtype)(x)
        x = nn.relu(x)
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype)(x)
        return x.astype(jnp.float32)


def vgg16(num_classes: int = 1000, compute_dtype=jnp.float32) -> VGG:
    return VGG(num_classes=num_classes, compute_dtype=compute_dtype)


def init_vgg16(key, image_size: int = 224, num_classes: int = 1000, compute_dtype=jnp.float32):
    model = vgg16(num_classes, compute_dtype)
    params = model.init(key, jnp.zeros((1, image_size, image_size, 3), jnp.float32))
    return model, params["params"]


def vgg_loss_fn(model: VGG):
    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply({"params": params}, x)
        return jnp.mean(softmax_cross_entropy(logits, y))

    return loss_fn
