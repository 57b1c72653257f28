"""The engine's one reconfigure transaction (``ddp._reconfigure``), through
every entry that changes what the next step compiles: the four public
methods, the resume path's execution-mode flip, and the three knobs the
autotune session flips.

Whatever the entry, an accepted change leaves no record of the old program
behind (compiled step, captured flight program, predicted program, compiled
text), the next step captures the program of the *new* configuration, and a
change the static verifier rejects leaves the engine on the old
configuration.  A configuration restored is the program restored: the
lowered step is, source locations aside, the first build's.
"""

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu import analysis
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.algorithms.stale import StaleSyncAlgorithm
from bagua_tpu.analysis import StaticVerifyError
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.defs import BaguaHyperparameter
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import FlightRecorder, Telemetry
from bagua_tpu.service.autotune_session import AutotuneSession

LAYERS = [12, 16, 16, 4]


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32)),
        jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32)),
    )


def _engine(group, entry, telemetry=None):
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.1, momentum=0.9), entry.algorithm(),
        process_group=group, bucket_size_bytes=1 << 9, overlap=entry.overlap,
        telemetry=telemetry,
    )
    return ddp, ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))


def _other_plan(ddp):
    return ddp.impl.tensors_to_buckets(ddp._tree_template, 1 << 14, filter_fn=None)


# -- the entries ---------------------------------------------------------------


def _rebucket(ddp, state):
    ddp.rebucket(_other_plan(ddp))
    return state


def _precision_plan(ddp, state):
    assert ddp.apply_precision_plan(["int8"] * ddp.plan.num_buckets)
    return state


def _staleness(ddp, state):
    assert ddp.apply_staleness(2)
    return state


def _switch_algorithm(ddp, state):
    return ddp.switch_algorithm(state, "zero", reason="manual")


def _resume_overlap(ddp, state):
    payload = ddp.export_plan_payload()
    payload["config"]["overlap"] = False
    assert ddp.adopt_plan_payload(payload)
    return state


class _Client:
    """The autotune service as the session sees it, proposing ``hp``."""

    def __init__(self, hp):
        self.hp = hp

    def register_tensors(self, *args, **kwargs):
        pass

    def report_metrics(self, *args, **kwargs):
        pass

    def ask_hyperparameters(self, *args, **kwargs):
        return self.hp, False


def _session(**proposal):
    def change(ddp, state):
        hp = BaguaHyperparameter(**proposal)
        AutotuneSession(ddp, "model", client=_Client(hp), interval=1).tick(32)
        return state

    return change


def _exchange(program):
    return [r for r in program if r["phase"] != "hop"]


@dataclasses.dataclass
class Entry:
    change: Callable
    algorithm: Callable = GradientAllReduceAlgorithm
    overlap: Any = True
    #: what of the captured program's records the change moves, and the
    #: value it moves it to (None: the records read the same either side)
    facet: Any = None
    expected: Any = None


ENTRIES = {
    "rebucket": Entry(
        _rebucket,
        facet=lambda prog: len({r["bucket"] for r in prog}), expected=1,
    ),
    "apply_precision_plan": Entry(
        _precision_plan,
        algorithm=lambda: GradientAllReduceAlgorithm(wire_precision="auto"),
        overlap="auto",  # its residuals are per-bucket state: no overlap=True
        facet=lambda prog: {r["precision"] for r in prog}, expected={"int8"},
    ),
    "apply_staleness": Entry(
        _staleness, algorithm=lambda: StaleSyncAlgorithm(staleness_tau=0),
        overlap=False,
    ),
    "switch_algorithm": Entry(
        _switch_algorithm,
        facet=lambda prog: {r["algo"] for r in prog}, expected={"zero"},
    ),
    "resume_overlap": Entry(
        _resume_overlap,
        facet=lambda prog: {r["phase"] for r in prog}, expected={"mono"},
    ),
    "session_hierarchical": Entry(
        # a quantized ring crosses every rank when flat and only the nodes
        # when hierarchical: the hop count is where the flip shows
        _session(is_hierarchical_reduce=True),
        algorithm=lambda: GradientAllReduceAlgorithm(wire_precision="int8"),
        facet=lambda prog: {r["hops"] for r in prog if r["phase"] == "hop"},
        expected={1},
    ),
    "session_wire_dtype": Entry(_session(wire_bf16=True)),
    "session_overlap": Entry(
        _session(overlap=False),
        facet=lambda prog: {r["phase"] for r in prog}, expected={"mono"},
    ),
}


def _configuration(ddp):
    impl = ddp.impl
    return {
        "impl": id(impl), "plan": id(ddp.plan), "updater": id(ddp._sharded_updater),
        "overlap": ddp.overlap, "pending_reshard": ddp._pending_reshard,
        "source": ddp._plan_source,
        "hierarchical": getattr(impl, "hierarchical", None),
        "wire_dtype": getattr(impl, "wire_dtype", None),
        "bucket_precision": getattr(impl, "bucket_precision", None),
        "staleness_tau": getattr(impl, "staleness_tau", None),
    }


def _assert_program_of(ddp, program):
    """The captured program is the live configuration's: its algorithm,
    every bucket of its plan, its plan version, its wire precisions."""
    exchange = _exchange(program)
    assert {r["algo"] for r in exchange} == {ddp.impl.algo_name}
    assert {r["bucket"] for r in exchange} == set(range(ddp.plan.num_buckets))
    assert {r["plan_version"] for r in program} == {ddp.plan_version}
    if hasattr(ddp.impl, "bucket_precisions"):
        precisions = ddp.impl.bucket_precisions(ddp.plan)
        assert all(r["precision"] == str(precisions[r["bucket"]]) for r in exchange)


@pytest.mark.parametrize("name", ENTRIES)
def test_change_drops_every_record_and_the_next_step_captures_anew(group, name):
    entry = ENTRIES[name]
    flight = FlightRecorder(capacity=256, rank=0, world_size=1)
    ddp, state = _engine(group, entry, Telemetry(flight=flight))
    try:
        state, _ = ddp.train_step(state, _batch())
        variant = ddp.last_variant
        old = ddp.flight_program(variant)
        assert old and ddp.compiled_step(variant) is not None
        _assert_program_of(ddp, old)

        state = entry.change(ddp, state)
        for of_variant in (ddp.compiled_step, ddp.flight_program, ddp.predicted_program):
            assert of_variant(variant) is None, of_variant.__name__

        state, losses = ddp.train_step(state, _batch(1))
        assert np.isfinite(np.asarray(losses)).all()
        new = ddp.flight_program()
        assert new and new is not old, "the old program is still replayed"
        _assert_program_of(ddp, new)
        if entry.facet is not None:
            assert entry.facet(old) != entry.expected
            assert entry.facet(new) == entry.expected
    finally:
        ddp.shutdown()


def test_a_kept_step_text_leaves_the_recorder_its_program(group):
    """The step is traced once, in the build and under the recorder's
    capture, and its text is read off the executable that was compiled
    there.  Until PR 47 ``keep_step_text`` lowered the step before its first
    dispatch, the dispatch then traced nothing, and a recorder beside a
    ``profile_dir`` replayed an empty program (ROADMAP D5)."""
    flight = FlightRecorder(capacity=256, rank=0, world_size=1)
    ddp, state = _engine(group, ENTRIES["rebucket"], Telemetry(flight=flight))
    ddp.keep_step_text = True
    try:
        state, _ = ddp.train_step(state, _batch())
        assert ddp.step_text() == ddp.compiled_step().as_text()
        assert ddp.flight_program()
        _assert_program_of(ddp, ddp.flight_program())
    finally:
        ddp.shutdown()


@pytest.mark.parametrize("name", ENTRIES)
def test_rejected_change_leaves_the_old_configuration(group, name, monkeypatch):
    entry = ENTRIES[name]
    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    verify, rejecting = analysis.verify_step_program, []

    def gate(*args, **kwargs):
        if rejecting:
            raise StaticVerifyError([])
        return verify(*args, **kwargs)

    monkeypatch.setattr(analysis, "verify_step_program", gate)
    flight = FlightRecorder(capacity=256, rank=0, world_size=1)
    ddp, state = _engine(group, entry, Telemetry(flight=flight))
    try:
        state, _ = ddp.train_step(state, _batch())
        variant, old = ddp.last_variant, ddp.flight_program()
        before = _configuration(ddp)

        rejecting.append(True)
        for _ in range(2):  # a second attempt is rejected like the first
            with pytest.raises(StaticVerifyError):
                entry.change(ddp, state)
            assert _configuration(ddp) == before
            assert ddp.compiled_step(variant) is None
        rejecting.clear()

        state, losses = ddp.train_step(state, _batch(1))
        assert np.isfinite(np.asarray(losses)).all()
        # the version names an adoption, also one rolled back; the rest of
        # the program dispatched is the old one
        unversioned = lambda prog: [
            {k: v for k, v in r.items() if k != "plan_version"} for r in prog
        ]
        assert unversioned(ddp.flight_program()) == unversioned(old)
        assert _configuration(ddp) == before
    finally:
        ddp.shutdown()


# -- a configuration restored is the program restored -------------------------


def _lowered(ddp, state):
    step = ddp._build_step(ddp.impl.step_variant(0))
    return step.lower(state, _batch()).as_text()  # carries no source locations


def _there_and_back_rebucket(ddp, state, monkeypatch):
    first = ddp.plan
    ddp.rebucket(_other_plan(ddp))
    assert ddp.plan.num_buckets != first.num_buckets
    ddp.rebucket(first)
    return state


def _there_and_back_switch(ddp, state, monkeypatch):
    state = ddp.switch_algorithm(state, "zero", reason="manual")
    assert ddp.impl.algo_name == "zero"
    return ddp.switch_algorithm(state, "gradient_allreduce", reason="manual")


def _there_and_back_precision(ddp, state, monkeypatch):
    assert ddp.apply_precision_plan(["int8"] * ddp.plan.num_buckets)
    assert ddp.apply_precision_plan(None)
    return state


def _rejected_and_rolled_back(ddp, state, monkeypatch):
    def reject(*args, **kwargs):
        raise StaticVerifyError([])

    with monkeypatch.context() as rejecting:
        rejecting.setattr(analysis, "verify_step_program", reject)
        for name in ("rebucket", "switch_algorithm", "session_wire_dtype", "session_overlap"):
            with pytest.raises(StaticVerifyError):
                ENTRIES[name].change(ddp, state)
    return state


@pytest.mark.parametrize("trip, engine", [
    (_there_and_back_rebucket, "rebucket"),
    (_there_and_back_switch, "switch_algorithm"),
    (_there_and_back_precision, "apply_precision_plan"),
    (_rejected_and_rolled_back, "rebucket"),
], ids=["rebucket", "switch_algorithm", "precision_plan", "rejected"])
def test_configuration_restored_is_the_program_restored(group, trip, engine, monkeypatch):
    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    ddp, state = _engine(group, ENTRIES[engine])
    ddp.keep_step_text = True
    try:
        state, _ = ddp.train_step(state, _batch())  # the gate has seen a batch
        first, variant = _lowered(ddp, state), ddp.last_variant
        assert ddp.step_text(variant) and ddp.predicted_program(variant)
        state = trip(ddp, state, monkeypatch)
        assert ddp.step_text(variant) is None and ddp.predicted_program(variant) is None
        assert _lowered(ddp, state) == first
        state, losses = ddp.train_step(state, _batch(1))
        assert np.isfinite(np.asarray(losses)).all()
    finally:
        ddp.shutdown()
