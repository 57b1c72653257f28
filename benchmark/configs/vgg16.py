"""Adapter for ``vgg16``: builds the program's model and loss through
``bagua_tpu.models``, maps the benchmark's seeded weights (in the layout of
``reference/vgg16.py``) onto the program's parameter tree, draws a batch,
and counts the operations of one sample."""

import jax
import jax.numpy as jnp

#: the leaf nearest the loss, in the program's tree
HEAD_LEAF = "['Dense_2']['kernel']"


def sizes(config, traffic_input):
    out = {k: config[k] for k in ("plan", "classifier_width", "num_classes")}
    out["image_size"] = traffic_input["image_size"]
    return out


def build_loss(sz):
    from bagua_tpu.models.vgg import VGG, vgg_loss_fn

    return vgg_loss_fn(VGG(
        num_classes=sz["num_classes"], cfg=tuple(sz["plan"]),
        compute_dtype=jnp.bfloat16, classifier_width=sz["classifier_width"],
    ))


def as_stored(ref_params):
    """Every parameter is stored in float32: nothing to round."""
    return ref_params


def to_program(tree, sz, cast=True):
    """A tree in the reference's layout, rearranged into the program's
    (flax's numbered ``Conv_n`` and ``Dense_n``)."""
    out = {}
    for n, layer in enumerate(tree["conv"]):
        out[f"Conv_{n}"] = {"kernel": layer["w"], "bias": layer["b"]}
    for n, layer in enumerate(tree["fc"]):
        out[f"Dense_{n}"] = {"kernel": layer["w"], "bias": layer["b"]}
    return out


def draw_batch(key, n, sz):
    """``n`` images uniform in [0, 1) and as many labels."""
    k_x, k_y = jax.random.split(key)
    side = sz["image_size"]
    return (jax.random.uniform(k_x, (n, side, side, 3), jnp.float32),
            jax.random.randint(k_y, (n,), 0, sz["num_classes"], jnp.int32))


def train_flops_per_sample(sz):
    """Floating-point operations one image needs in a training step: the
    forward pass's convolutions and matrix multiplications at two operations
    per multiply-add, times three for forward and backward.  Nothing
    recomputed, nothing elementwise."""
    side, cin, ops = sz["image_size"], 3, 0
    for v in sz["plan"]:
        if v == "M":
            side //= 2
        else:
            ops += 2 * side * side * 9 * cin * v
            cin = v
    w = sz["classifier_width"]
    ops += 2 * (side * side * cin * w + w * w + w * sz["num_classes"])
    return 3.0 * ops
