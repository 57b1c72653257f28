"""The one cross-entropy of the model zoo, written from the logits.

A loss that takes ``log_softmax(logits)`` and then gathers the label's
entry writes a second vocabulary-sized array to read one value a row of it:
on BERT-Large's head (4096 x 30522 f32) that was 500 MB and 1.51 ms a step
(PR 28, ``PERF.md`` section 6).  Here the only vocabulary-sized array either
pass holds is the logits.  The gradient is ``softmax - onehot`` scaled by the
cotangent, and how the label's logit is picked decides what the compiler
makes of the ``onehot`` half (the compiled steps, ``PERF.md`` section 6,
PR 37):

* picked by ``take_along_axis``, a gather whose gradient is a scatter.  At
  ``[32, 128, V]`` (BERT) the compiler turns that scatter into a select
  inside the operands of the two gradient matmuls.  At ``[1, 8192, V]`` (the
  three expert cells) it keeps it a scatter: ``softmax * g`` is written as
  tokens x vocabulary float32 (537 to 634 MB), re-tiled flat, scattered
  into, and converted to bf16 before ``dX`` and ``dW`` read it, 5.1 to 5.9 ms
  a step with no name (``copy.187`` and ``fusion.439`` of
  ``lfm2-8b-a1b.dp1-s8192``, 1.61 and 1.60 ms; ledger, PR 36);
* picked by a one-hot select and a row sum, as here.  The gradient is
  ``where(hit, g, 0)``, element-wise, and at both shapes ``dX`` and ``dW``
  take the float32 logits, the row's log-sum-exp and the compare as operands
  of their own fusions: no ``dlogits`` array, no scatter.  The products
  take on 0.2 to 0.9 ms of it, and the expert cells' steps are 4.4 to 7.0 ms
  shorter (of 130, 172 and 223).  At BERT's shape the row sum joins the bias
  gradient's pass over the logits (+0.1%).

One caller takes it several times a step and weights its result a position:
``models/ouro.py`` calls it once an exit, four times on float32 logits of
``[1, 8192, 49152]`` (1.61 GB each, 2.5 times the columns of any shape above),
and multiplies the per-position results by an exit distribution the model
computes, so the cotangent ``g`` is a different number in every row, not the
mean's one constant.  Since PR 43 that caller differentiates it *inside the
forward pass* (``ouro._exit_sum``: ``jax.vjp`` of the head's product and this
function, pulled back along the rows' weights where the logits have just been
written), so nothing of it runs in the backward pass and nothing is rebuilt
(PR 42 rebuilt each call under ``jax.checkpoint``); an exit keeps the two
products' results and the per-position values, 0.23 GB.  At that shape the
compiled step holds no ``dlogits`` array and no scatter either way: the
logits, the row statistics, the compare and ``g`` are operands of the two
gradient products' fusions (``PERF.md`` section 6, PR 42 and PR 43).
"""

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example ``-log_softmax(logits)[..., label]`` over the last axis, in
    the logits' dtype: the row's log-sum-exp (max-shifted) minus the label's
    logit, which is a sum of that logit and zeros.  ``labels`` has the logits'
    shape without the last axis and lies in ``[0, V)``: a label outside picks
    nothing and the row reads its log-sum-exp, where a gather would clamp.
    Callers take their own mean."""
    hit = labels[..., None] == jnp.arange(logits.shape[-1])
    picked = jnp.sum(jnp.where(hit, logits, 0), axis=-1)
    return jax.nn.logsumexp(logits, axis=-1) - picked
