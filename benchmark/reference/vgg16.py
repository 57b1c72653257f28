"""Plain reference for VGG (configuration D of arXiv:1409.1556 is VGG16):
forward pass and loss in straightforward ``jax.numpy``/``jax.lax``, float32.
It imports nothing of ``bagua_tpu``.

Departures from the paper, each because the job that is benchmarked has it
(``configs/vgg16.json`` lists them under ``departures``): no dropout in the
classifier, NHWC layout, weights drawn with variance 1/fan_in (the paper
pre-trains a shallower net to initialise this one).
"""

import jax
import jax.numpy as jnp


def layer_shapes(sizes):
    """``(conv kernel shapes, dense kernel shapes)`` for ``sizes``."""
    convs, cin, side = [], 3, sizes["image_size"]
    for v in sizes["plan"]:
        if v == "M":
            side //= 2
        else:
            convs.append((3, 3, cin, v))
            cin = v
    w = sizes["classifier_width"]
    dense = [(side * side * cin, w), (w, w), (w, sizes["num_classes"])]
    return convs, dense


def init_params(key, sizes):
    convs, dense = layer_shapes(sizes)
    keys = iter(jax.random.split(key, len(convs) + len(dense)))

    def layer(shape):
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        w = jax.random.normal(next(keys), shape, jnp.float32) * fan_in ** -0.5
        return {"w": w, "b": jnp.zeros(shape[-1:], jnp.float32)}

    return {"conv": [layer(s) for s in convs], "fc": [layer(s) for s in dense]}


def max_pool_2x2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def loss(params, batch, sizes):
    """Mean cross entropy over the batch's images."""
    x, y = batch
    convs = iter(params["conv"])
    for v in sizes["plan"]:
        if v == "M":
            x = max_pool_2x2(x)
        else:
            layer = next(convs)
            x = jax.lax.conv_general_dilated(
                x, layer["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            x = jax.nn.relu(x + layer["b"])
    x = x.reshape(x.shape[0], -1)
    for n, layer in enumerate(params["fc"]):
        x = x @ layer["w"] + layer["b"]
        if n < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    logp = jax.nn.log_softmax(x)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
