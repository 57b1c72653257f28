"""How ``correct`` is decided for a training cell.

The program's first ``CHECKED_STEPS`` steps, driven through ``Trainer.fit`` by
the harness, are compared with as many steps of the configuration's plain
reference from the same seeded weights on the same batches:

``loss_gap``         largest ``|loss - reference loss|`` over the checked steps
``grad_rel_err``     ``||g - g_ref|| / ||g_ref||`` of the first gradient as the
                     optimizer got it, over all compared leaves together
``head_rel_err``     the same on the one leaf nearest the loss (the adapter's
                     ``HEAD_LEAF``, the output layer's kernel): its gradient
                     carries the whole forward pass's precision and none of
                     the backward pass's ReLU and max-pool flips, which under
                     bfloat16 move a VGG's early gradients by a tenth
``grad_norm_gap``    worst leaf's ``| ||g|| - ||g_ref|| |`` over the larger of
                     that leaf's and the median leaf's ``||g_ref||``
``update_norm_gap``  the same for the parameters' change after the checked steps

Compared leaves are those the program stores in float32: there the first
update is ``-lr * g`` exactly, so the gradient can be read back from the
parameters.  Each number has a limit of its own in the configuration's JSON,
set from sound runs and from the control (``lower_precision``) as PERF.md
records.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import optax

#: steps the reference follows.  Two, not the three warm-up steps: the
#: reference multiplies in full float32 (six bfloat16 passes on the chip) and
#: every run of every later check pays its time
CHECKED_STEPS = 2
#: mantissa bits of float8_e4m3, the precision below bfloat16's seven
CONTROL_MANTISSA_BITS = 3


def make_optimizer(spec: dict) -> optax.GradientTransformation:
    if spec["name"] == "sgd":
        return optax.sgd(spec["learning_rate"], momentum=spec.get("momentum"))
    raise ValueError(f"no optimizer {spec['name']!r}")


def lower_precision(loss_fn):
    """The control: ``loss_fn`` with every weight of two or more dimensions
    rounded to the three mantissa bits of ``float8_e4m3`` (gradients pass
    straight through), the precision below the bfloat16 the configurations
    state.  The exponent keeps its range, as a scale per tensor would give
    it: this is the mildest form the step to 8 bits can take.  It wraps the
    reference for the chip readings and the program's loss in the tests; it
    is never a switch of the program.

    ``reduce_precision`` and not a cast there and back: XLA may drop such a
    pair (``xla_allow_excess_precision``), and on the chip it did (PR 25)."""
    def rounded(w):
        if w.ndim < 2 or not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        low = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=CONTROL_MANTISSA_BITS)
        return w + jax.lax.stop_gradient(low - w)

    def wrapped(params, *args):
        return loss_fn(jax.tree.map(rounded, params), *args)

    return wrapped


def reference_steps(loss_fn, params, batches, optimizer, micro: int, highest: bool = True,
                    place=lambda part: part):
    """One optimizer step of ``loss_fn(params, batch)`` from ``params`` for
    each of ``batches``, each batch evaluated in micro-batches of ``micro``
    rows, each put where ``place`` puts it.  Returns the per-step losses, the
    first gradient and the parameters' total change.  ``highest`` multiplies
    in full float32."""
    value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    accumulate = jax.jit(
        lambda acc, part: jax.tree.map(jnp.add, acc, part), donate_argnums=0)

    @jax.jit
    def apply(p, opt_state, g):
        updates, opt_state = optimizer.update(g, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    start, opt_state = params, optimizer.init(params)
    losses, first_grad = [], None
    precision = jax.default_matmul_precision("highest") if highest else contextlib.nullcontext()
    with precision:
        for batch in batches:
            rows = jax.tree.leaves(batch)[0].shape[0]
            if rows % micro:
                raise ValueError(f"{rows} rows do not divide into micro-batches of {micro}")
            parts = rows // micro
            total = None
            for k in range(parts):
                part = place(jax.tree.map(lambda x: x[k * micro:(k + 1) * micro], batch))
                out = value_and_grad(params, part)
                total = out if total is None else accumulate(total, out)
            loss, grad = jax.tree.map(lambda x: x / parts, total)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = grad
            params, opt_state = apply(params, opt_state, grad)
    delta = jax.tree.map(jnp.subtract, params, start)
    return losses, first_grad, delta


def checked_leaves(tree, stored):
    """``{path: leaf}`` of the leaves the program stores in float32.
    ``tree`` and ``stored`` are in the program's layout."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    kinds = jax.tree.leaves(stored)
    return {
        jax.tree_util.keystr(path): leaf
        for (path, leaf), kind in zip(flat, kinds) if kind.dtype == jnp.float32
    }


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float32).astype(np.float64).ravel()))


def _worst_norm_gap(got: dict, want: dict):
    """``(gap, leaf)`` of the leaf whose norm lies farthest from the
    reference's, against the larger of that leaf's and the median leaf's
    reference norm (some gradients are all but zero)."""
    floor = float(np.median(list(want.values())))
    return max((abs(got[k] - want[k]) / max(want[k], floor), k) for k in want)


def compare(losses, grad, update_norms, ref_losses, ref_grad, ref_update_norms, head: str):
    """``(numbers compared, worst leaf of each norm gap)``.  ``grad`` and
    ``ref_grad`` are ``{path: array}`` over the checked leaves, the
    ``*_norms`` ``{path: float}``, ``head`` the path of the leaf nearest the
    loss."""
    if set(grad) != set(ref_grad) or set(update_norms) != set(ref_update_norms):
        raise ValueError("program and reference disagree on the compared leaves")
    diff2 = ref2 = 0.0
    norms, ref_norms, leaf_err = {}, {}, {}
    for k, want in ref_grad.items():
        want = np.asarray(want, np.float32).astype(np.float64)
        got = np.asarray(grad[k], np.float32).astype(np.float64)
        diff2 += float(np.sum(np.square(got - want)))
        ref2 += float(np.sum(np.square(want)))
        norms[k], ref_norms[k] = float(np.linalg.norm(got)), float(np.linalg.norm(want))
        leaf_err[k] = float(np.linalg.norm(got - want)) / max(ref_norms[k], 1e-30)
    grad_gap, grad_leaf = _worst_norm_gap(norms, ref_norms)
    update_gap, update_leaf = _worst_norm_gap(update_norms, ref_update_norms)
    numbers = {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref_losses)),
        "grad_rel_err": (diff2 / ref2) ** 0.5,
        "head_rel_err": leaf_err[head],
        "grad_norm_gap": grad_gap,
        "update_norm_gap": update_gap,
    }
    return numbers, {"grad_norm_gap": grad_leaf, "update_norm_gap": update_leaf,
                     "leaf_rel_err": leaf_err, "leaf_ref_norm": ref_norms}


def leaf_norms(leaves: dict) -> dict:
    return {k: _norm(v) for k, v in leaves.items()}


def verdict(numbers: dict, limits: dict):
    """``(correct, lines)``: every number within its limit, and one printed
    line per number beside its limit.  A number with no limit on record, or
    one that is not finite, is not correct."""
    ok, lines = True, []
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        within = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(within)
        lines.append(f"check {name}={value:.6g} limit={limit} {'ok' if within else 'NOT WITHIN'}")
    return ok, lines
