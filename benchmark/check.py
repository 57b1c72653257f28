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
import functools

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


def _leaf_by_leaf(fn, tree, *rest):
    """``jax.tree.map(fn, tree, *rest)`` one leaf at a time, each source leaf
    deleted from the device once its result stands there: the trees are given
    up, and no more than one leaf of them stands twice.  Waiting for each
    result is what bounds it: a buffer is released only when the operation
    that reads it has run, and the host would otherwise allocate every
    result first."""
    leaves, treedef = jax.tree.flatten(tree)
    columns = [leaves] + [treedef.flatten_up_to(other) for other in rest]
    out = []
    for sources in zip(*columns):
        out.append(jax.block_until_ready(fn(*sources)))
        for source in sources:
            source.delete()
    return treedef.unflatten(out)


def reference_steps(loss_fn, make_params, batches, optimizer, micro: int, highest: bool = True,
                    place=lambda part: part, keep=lambda grad: grad):
    """One optimizer step of ``loss_fn(params, batch)`` from ``make_params()``
    for each of ``batches``, each batch evaluated in micro-batches of
    ``micro`` rows, each put where ``place`` puts it.  Returns the per-step
    losses, what ``keep`` makes of the first gradient (it is called before
    the second step, and the harness takes the gradient to the host there)
    and the parameters' total change.  ``highest`` multiplies in full float32.

    On the device stand the parameters, the optimizer's state, the gradient
    being made and at most one more tree of their size: three copies with
    plain SGD, four with momentum, beside ``loss_fn``'s own step.  The start
    does not live through the steps: ``make_params`` is called a second time
    after the last of them, and the change is taken leaf by leaf."""
    value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    accumulate = jax.jit(
        lambda acc, part: jax.tree.map(jnp.add, acc, part), donate_argnums=0)

    # the gradient is not donated: with two results and three arguments one
    # donation could not be used, and the default ``keep`` keeps the first
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(p, opt_state, g):
        updates, opt_state = optimizer.update(g, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    params = make_params()
    opt_state = optimizer.init(params)
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
                # waited for and dropped, or the next micro-batch's gradient is made beside it
                total = out if total is None else jax.block_until_ready(accumulate(total, out))
                del out
            loss, grad = _leaf_by_leaf(lambda x: x / parts, total)
            del total
            losses.append(float(loss))
            if len(losses) == 1:
                first_grad = keep(grad)
            params, opt_state = apply(params, opt_state, grad)
            del grad
    del opt_state
    delta = _leaf_by_leaf(jnp.subtract, params, make_params())
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
