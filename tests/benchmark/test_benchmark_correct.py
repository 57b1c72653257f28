"""``correct`` has been shown to fail: the control (the reference in the
next lower precision, put in the program's place), the program itself with
8-bit weights, and a run whose timed path is broken underneath.  At the
configurations' toy sizes on the CPU, against the toy limits in their JSON;
the chip's readings at the cells' own sizes are in PERF.md."""

import json
import time

import jax
import jax.numpy as jnp
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
