"""The one cross-entropy of the model zoo, written from the logits.

A loss that takes ``log_softmax(logits)`` and then gathers the label's
entry writes a second vocabulary-sized array to read one value a row of it:
on BERT-Large's head (4096 x 30522 f32) that was 500 MB and 1.51 ms a step
(PR 28, ``PERF.md`` section 6).  Here the only vocabulary-sized array the
forward pass holds is the logits; autodiff's gradient of this form is
``softmax - onehot`` scaled by the cotangent, which the compiler builds
inside the operands of the two gradient matmuls from the logits alone.
"""

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example ``-log_softmax(logits)[..., label]`` over the last axis, in
    the logits' dtype: the row's log-sum-exp (max-shifted) minus the picked
    logit.  ``labels`` has the logits' shape without the last axis; callers
    take their own mean."""
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked
