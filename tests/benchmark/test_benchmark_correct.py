"""``correct`` has been shown to fail: the control (the reference in the
next lower precision, put in the program's place), the program itself with
8-bit weights, and a run whose timed path is broken underneath.  And what the
check itself holds on the device while it follows the steps: three trees of
the parameters' size with plain SGD, four with momentum, and the numbers of
the six-copy form it replaced, bit for bit.  At the configurations' toy sizes
on the CPU, against the toy limits in their JSON; the chip's readings at the
cells' own sizes are in PERF.md."""

import contextlib
import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import check, harness, manifest
from benchmark import run as bench_run

ONE_CHIP_CELLS = ["bert-large.dp1", "vgg16.dp1"]
SEEDS = [2_400_000_011, 17, 2**31 + 5]


def toy_run(name, seed, patch_adapter=None):
    """Set-up of a dry run on the suite's CPU devices, without a window."""
    cell = manifest.load_cell(name, dry=True)
    if patch_adapter is not None:
        patch_adapter(cell.adapter)
    run = harness.Run(cell, seed, time.perf_counter(), jax.devices()[:cell.chips])
    with harness.closing_run(run):
        run.build()
        run.setup()
        run.watcher.close()
        run.free_program()
    return cell, run


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_the_stated_precision_passes_and_the_control_does_not(name, seed):
    cell, run = toy_run(name, seed)
    ref = run.reference()
    limits = cell.tolerances
    sound = run.numbers(ref)
    passed, lines = check.verdict(sound, limits)
    assert passed, lines
    control, _ = check.compare(*run.reference(control=True), *ref, head=cell.adapter.HEAD_LEAF)
    passed, lines = check.verdict(control, limits)
    assert not passed, lines
    # the number that separates the two precisions in both configurations
    assert control["head_rel_err"] > limits["head_rel_err"]["limit"] > sound["head_rel_err"]


@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_the_program_with_8_bit_weights_does_not_pass(name):
    """The same lowering applied to the program's own loss, driven through
    ``Trainer.fit`` like any run: the step a later PR might be tempted by."""
    def patch(adapter):
        build = adapter.build_loss
        adapter.build_loss = lambda sizes: check.lower_precision(build(sizes))

    cell, run = toy_run(name, SEEDS[0], patch_adapter=patch)
    passed, lines = check.verdict(run.numbers(run.reference()), cell.tolerances)
    assert not passed, lines


# -- what the check holds, and that its numbers did not move -------------------


def six_copy_reference_steps(loss_fn, params, batches, optimizer, micro, highest=True,
                             place=lambda part: part):
    """``check.reference_steps`` as it stood until PR 44, the plain oracle of
    the two tests below: the start, the parameters, the first gradient, the
    summed gradient, its mean and the step's result all on the device at once."""
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
            parts = jax.tree.leaves(batch)[0].shape[0] // micro
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


#: ``(the cell whose toy reference is the loss, under its own optimizer; as the control)``
STEPPED = {
    "sgd": ("bert-large.dp1", False),
    "momentum": ("vgg16.dp1", False),
    "control": ("bert-large.dp1", True),
}


MOMENTUM = {"name": "sgd", "learning_rate": 0.01, "momentum": 0.9}


def toy_steps(kind, rows, seed=SEEDS[0], optimizer=None):
    """``(loss_fn, make_params, batches, optimizer, highest)`` of a toy cell's
    plain reference over ``check.CHECKED_STEPS`` batches of ``rows`` rows,
    under the cell's own optimizer or the one given."""
    name, control = STEPPED[kind]
    cell = manifest.load_cell(name, dry=True)
    sizes = cell.sizes
    params_key, data_key = jax.random.split(jax.random.PRNGKey(seed))
    init = jax.jit(lambda k: cell.adapter.as_stored(cell.reference.init_params(k, sizes)))
    batches = [cell.adapter.draw_batch(jax.random.fold_in(data_key, k), rows, sizes)
               for k in range(check.CHECKED_STEPS)]

    def loss_fn(params, batch):
        return cell.reference.loss(params, batch, sizes)

    return (check.lower_precision(loss_fn) if control else loss_fn, lambda: init(params_key),
            batches, check.make_optimizer(optimizer or cell.config["optimizer"]), not control)


@pytest.mark.parametrize("micro", [6, 2], ids=["one_micro_batch", "three_micro_batches"])
@pytest.mark.parametrize("kind", sorted(STEPPED))
def test_the_three_copy_steps_give_the_six_copy_steps_numbers_bit_for_bit(kind, micro):
    loss_fn, make_params, batches, optimizer, highest = toy_steps(kind, rows=6)
    want = six_copy_reference_steps(loss_fn, make_params(), batches, optimizer, micro, highest)
    got = check.reference_steps(loss_fn, make_params, batches, optimizer, micro, highest)
    assert got[0] == want[0] and len(got[0]) == check.CHECKED_STEPS
    for found, expected in zip(got[1:], want[1:]):
        found, expected = jax.tree.leaves(found), jax.tree.leaves(expected)
        assert len(found) == len(expected) > 0
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(found, expected))
    # the steps moved the parameters, and the second batch is not the first
    assert got[0][0] != got[0][1] and any(np.any(np.asarray(x)) for x in jax.tree.leaves(got[2]))


def live_bytes():
    return sum(x.nbytes for x in jax.live_arrays() if not x.is_deleted())


def trees_held(steps, optimizer, micro):
    """``({where: the most held there}, allowance)``: what the device holds
    while ``steps`` follows a toy cell's two steps, in trees of the
    parameters' size and beyond what it held before.  Read where a
    micro-batch is placed (``place``: what stands as its gradient is about
    to be made), from inside the loss (``loss``: a callback, so at every
    evaluation, the gradient being made counted or not as the runtime has
    it), where the first gradient is handed over (``keep``) and where the
    start is made again (``params``).  The allowance is the micro-batches
    themselves: BERT's token ids, a hundredth of a tree at toy sizes."""
    loss_fn, make_params, batches, optimizer, highest = toy_steps("sgd", 6, optimizer=optimizer)
    tree_bytes = sum(x.nbytes for x in jax.tree.leaves(make_params()))
    seen = []

    def census(where):
        seen.append((where, (live_bytes() - before) / tree_bytes))

    def watched_loss(params, batch):
        jax.debug.callback(lambda: census("loss"))
        return loss_fn(params, batch)

    def watched_params():
        census("params")
        return make_params()

    def watched_place(part):
        census("place")
        return part

    def to_host(grad):
        census("keep")
        return jax.device_get(grad)

    gc.collect()
    before = live_bytes()
    if steps is check.reference_steps:
        out = steps(watched_loss, watched_params, batches, optimizer, micro, highest,
                    place=watched_place, keep=to_host)
    else:
        out = steps(watched_loss, watched_params(), batches, optimizer, micro, highest,
                    place=watched_place)
    jax.effects_barrier()
    del out
    evaluations = check.CHECKED_STEPS * (6 // micro)
    assert [where for where, _ in seen].count("loss") == evaluations
    assert [where for where, _ in seen].count("place") == evaluations
    assert min(held for where, held in seen if where != "params") > 0.99  # the parameters at least
    most = {where: max(held for at, held in seen if at == where) for where, _ in seen}
    return most, sum(x.nbytes for x in jax.tree.leaves(batches[0])) / tree_bytes + 0.01


@pytest.mark.parametrize("micro", [6, 2], ids=["one_micro_batch", "three_micro_batches"])
@pytest.mark.parametrize("optimizer,limit", [(None, 3), (MOMENTUM, 4)], ids=["sgd", "momentum"])
def test_the_check_holds_three_trees_with_sgd_and_four_with_momentum(optimizer, limit, micro):
    """Parameters, the optimizer's state, the gradient being made and at
    most one more tree."""
    most, allowance = trees_held(check.reference_steps, optimizer, micro)
    assert allowance < 0.02
    assert most["place"] + 1 <= limit + allowance  # and the gradient about to be made
    assert max(most.values()) <= limit + allowance
    assert most["params"] <= 1 + allowance  # the start is made again beside the result alone
    # the census has been seen to count more: the form it replaced holds the start, the
    # first gradient and the last step's sum beside them
    six_copy, _ = trees_held(six_copy_reference_steps, optimizer, micro)
    assert six_copy["place"] + 1 >= limit + 2


def test_the_steps_give_up_the_trees_they_consume():
    """The parameters the steps made are not on the device afterwards: what
    is left is the change and what ``keep`` kept."""
    loss_fn, make_params, batches, optimizer, highest = toy_steps("momentum", rows=6)
    made = []

    def remembered():
        made.append(make_params())
        return made[-1]

    _, grad, delta = check.reference_steps(loss_fn, remembered, batches, optimizer, 2, highest)
    assert len(made) == 2  # the start, and the start again for the change
    assert all(x.is_deleted() for tree in made for x in jax.tree.leaves(tree))
    assert not any(x.is_deleted() for x in jax.tree.leaves((grad, delta)))


def drive(capsys, name):
    """``benchmark/run.py`` in this process on the suite's CPU devices:
    ``--dry-run`` skips the look for a chip and drives the rest of a run."""
    assert bench_run.main(["--workload", name, "--seed", "23", "--seconds", "1",
                           "--trace", "0", "--dry-run"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    from bagua_tpu.ddp import DistributedDataParallel

    real = DistributedDataParallel.train_step

    def frozen(self, state, batch):
        kept = jax.tree.map(jnp.copy, state.params)
        new_state, losses = real(self, state, batch)
        return new_state._replace(params=kept), losses

    monkeypatch.setattr(DistributedDataParallel, "train_step", frozen)
    result = drive(capsys, "vgg16.dp1")
    assert result["correct"] is False
    assert result["checks"]["update_norm_gap"] == pytest.approx(1.0)
    assert result["checks"]["grad_rel_err"] == pytest.approx(1.0)


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    from bagua_tpu.ddp import DistributedDataParallel

    real = DistributedDataParallel.train_step

    def half(self, state, batch):
        rows = jax.tree.leaves(batch)[0].shape[0]
        batch = jax.tree.map(lambda x: jnp.concatenate([x[:rows // 2]] * 2), jax.device_get(batch))
        return real(self, state, self.shard_batch(batch))

    monkeypatch.setattr(DistributedDataParallel, "train_step", half)
    result = drive(capsys, "bert-large.dp1")
    assert result["correct"] is False
    assert result["checks"]["grad_rel_err"] > 0.3


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch, capsys):
    """``dp_filter`` is the program's own way to keep a leaf's gradient
    local; with every leaf kept local each rank steps on its own quarter."""
    real = manifest.load_cell

    def without_exchange(name, dry=False):
        cell = real(name, dry=dry)
        cell.traffic = {**cell.traffic, "trainer": {"dp_filter": lambda leaf: False}}
        return cell

    monkeypatch.setattr(manifest, "load_cell", without_exchange)
    result = drive(capsys, "bert-large.dp4")
    assert result["correct"] is False
    assert result["checks"]["replica_mismatches"] > 0
    assert result["checks"]["grad_rel_err"] > 0.3


def test_an_unbroken_run_in_this_process_is_correct(capsys):
    assert drive(capsys, "bert-large.dp4")["correct"] is True
