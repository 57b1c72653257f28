"""Minimal MLP used by algorithm-correctness tests.

The analog of the small nets in the reference's algorithm tests
(``tests/torch_api/test_gradient_allreduce.py:21-35``): two hidden layers,
plain pytree params, pure functions — so tests don't depend on a module
framework and oracles are easy to write in numpy.
"""

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from bagua_tpu.models.losses import softmax_cross_entropy


def init_mlp(key, sizes: Sequence[int]) -> Dict[str, Dict[str, jnp.ndarray]]:
    """He-initialized MLP: ``sizes = [in, h1, ..., out]``."""
    params = {}
    keys = jax.random.split(key, len(sizes) - 1)
    for i, (k, (fan_in, fan_out)) in enumerate(zip(keys, zip(sizes[:-1], sizes[1:]))):
        params[f"layer{i}"] = {
            "w": jax.random.normal(k, (fan_in, fan_out), jnp.float32)
            * jnp.sqrt(2.0 / fan_in),
            "b": jnp.zeros((fan_out,), jnp.float32),
        }
    return params


def mlp_apply(params, x: jnp.ndarray) -> jnp.ndarray:
    n_layers = len(params)
    for i in range(n_layers):
        layer = params[f"layer{i}"]
        x = x @ layer["w"] + layer["b"]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def mse_loss(params, batch) -> jnp.ndarray:
    x, y = batch
    pred = mlp_apply(params, x)
    return jnp.mean((pred - y) ** 2)


def softmax_loss(params, batch) -> jnp.ndarray:
    x, y = batch
    logits = mlp_apply(params, x)
    return jnp.mean(softmax_cross_entropy(logits, y))
