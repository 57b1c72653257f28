"""Static collective-program verifier: the four checkers and the engine gate.

Adversarial half (the acceptance cases): programs with a rank-conditional
collective, a bucket whose wire bytes are off by one from the planner's
analytic model, and a stale exported plan version must each be **rejected at
trace time** by the right checker — named check, named source label — and,
when the strict gate is on, must never dispatch (the flight recorder stays
empty).

Positive half: real engines (gradient_allreduce, zero — every wire
precision the sweep covers lives in ``ci/static_verify.py``) pass strict
verification, and the statically predicted flight program equals the
recorder's capture record-for-record.
"""

import dataclasses
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import bagua_tpu  # noqa: F401  (grafts jax.shard_map on old jax)
from bagua_tpu.algorithms import build_algorithm
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.analysis import (
    StaticVerifyError,
    WireModelConfig,
    canonical_records,
    check_rank_invariance,
    check_wire_exactness,
    collect_ir,
    verify_step_program,
)
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability.flight_recorder import FlightRecorder
from bagua_tpu.observability.telemetry import Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = [64, 128, 128, 64]


def make_batch():
    rng = np.random.RandomState(0)
    return (
        jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32)),
        jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32)),
    )


def make_ddp(group, algo=None, overlap=False, telemetry=None, **kw):
    return DistributedDataParallel(
        mse_loss,
        optax.sgd(0.1, momentum=0.9),
        algo or build_algorithm("gradient_allreduce", lr=0.1),
        process_group=group,
        bucket_size_bytes=1 << 12,
        overlap=overlap,
        telemetry=telemetry,
        **kw,
    )


# ---------------------------------------------------------------------------
# Adversarial program 1: rank-conditional collective
# ---------------------------------------------------------------------------


def test_rank_conditional_psum_rejected_at_trace_time(group):
    """A psum under a ``lax.cond`` whose predicate derives from
    ``axis_index``: different ranks would take different branches around a
    collective — the first-desync class.  check_rank_invariance must reject
    it at trace time, attributing the enclosing branch."""

    def body(x):
        r = jax.lax.axis_index("intra")

        def exchange(v):
            return jax.lax.psum(v, "intra")

        def skip(v):
            return v * 4.0

        return jax.lax.cond(r == 0, exchange, skip, x)

    fn = group.shard_map(body, in_specs=(P("intra"),), out_specs=P("intra"))
    x = jnp.ones((8, 4), jnp.float32)
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))

    assert program.collectives, "psum not extracted from the cond branch"
    flagged = [d for d in program.collectives if d.rank_conditional]
    assert flagged, "collective not marked rank-conditional"

    findings = check_rank_invariance(program)
    errors = [f for f in findings if f.severity == "error"]
    assert errors, "rank-conditional psum was not rejected"
    assert all(f.check == "rank_invariance" for f in errors)
    # the finding names the branch the collective sits under
    assert any("cond" in (f.label or f.message) for f in errors)


def test_uniform_cond_psum_is_clean(group):
    """Control: the same cond-around-psum shape with a *rank-uniform*
    predicate (a scalar every rank computes identically, e.g. a step-count
    schedule) must verify clean — the taint analysis has to distinguish
    rank-derived from rank-uniform predicates, not ban lax.cond."""

    def body(x, step):
        def exchange(v):
            return jax.lax.psum(v, "intra")

        def skip(v):
            return v * 4.0

        return jax.lax.cond(step % 2 == 0, exchange, skip, x)

    fn = group.shard_map(
        body, in_specs=(P("intra"), P()), out_specs=P("intra")
    )
    x = jnp.ones((8, 4), jnp.float32)
    step = jnp.zeros((), jnp.int32)
    program, _ = collect_ir(fn, (x, step), dict(group.mesh.shape))

    assert program.collectives
    assert not [d for d in program.collectives if d.rank_conditional]
    assert not [
        f for f in check_rank_invariance(program) if f.severity == "error"
    ]


def test_subaxis_psum_does_not_launder_taint(group):
    """A psum over a *sub*-axis does not uniformize along the others: a
    predicate derived from ``axis_index('inter')`` stays inter-varying
    after a psum over 'intra' only, so branching on it around a collective
    must still be rejected (the false-negative class of whole-set
    laundering)."""

    def body(x):
        r = jax.lax.axis_index("inter")
        # reduces over 'intra' only: still differs across 'inter' ranks
        half_uniform = jax.lax.psum(r, "intra")

        def exchange(v):
            return jax.lax.psum(v, "intra")

        def skip(v):
            return v * 4.0

        return jax.lax.cond(half_uniform > 0, exchange, skip, x)

    fn = group.shard_map(body, in_specs=(P("intra"),), out_specs=P("intra"))
    x = jnp.ones((8, 4), jnp.float32)
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))

    flagged = [d for d in program.collectives if d.rank_conditional]
    assert flagged, "sub-axis psum laundered taint it must not launder"
    assert [f for f in check_rank_invariance(program) if f.severity == "error"]

    # control: laundering over BOTH axes is rank-uniform again
    def body_full(x):
        r = jax.lax.axis_index("inter")
        uniform = jax.lax.psum(jax.lax.psum(r, "intra"), "inter")

        def exchange(v):
            return jax.lax.psum(v, "intra")

        def skip(v):
            return v * 4.0

        return jax.lax.cond(uniform > 0, exchange, skip, x)

    fn = group.shard_map(
        body_full, in_specs=(P("intra"),), out_specs=P("intra")
    )
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))
    assert not [d for d in program.collectives if d.rank_conditional]


def test_while_cond_collective_recorded_and_flagged(group):
    """Collectives in a while loop's *predicate* jaxpr must enter the IR
    (wire census) and, under a rank-tainted predicate, the rank-invariance
    check — they used to be invisible to all four checkers."""

    def body(x):
        def cond_fn(c):
            i, v = c
            # a psum'd convergence residual in the loop predicate
            return jax.lax.psum(jnp.sum(v), "intra") > i

        def body_fn(c):
            i, v = c
            return i + 1, v * 0.5

        _, out = jax.lax.while_loop(cond_fn, body_fn, (jnp.float32(0.0), x))
        return out

    fn = group.shard_map(body, in_specs=(P("intra"),), out_specs=P("intra"))
    x = jnp.ones((8, 4), jnp.float32)
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))
    in_while = [d for d in program.collectives if "while" in d.path]
    assert in_while, "predicate psum missing from the IR"
    # uniform predicate (psum'd residual): legal, not rank-conditional
    assert not [d for d in program.collectives if d.rank_conditional]

    def body_tainted(x):
        def cond_fn(c):
            i, v = c
            return i < jax.lax.axis_index("intra")  # rank-varying trip count

        def body_fn(c):
            i, v = c
            return i + 1, jax.lax.psum(v, "intra")

        _, out = jax.lax.while_loop(cond_fn, body_fn, (jnp.int32(0), x))
        return out

    fn = group.shard_map(
        body_tainted, in_specs=(P("intra"),), out_specs=P("intra")
    )
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))
    flagged = [d for d in program.collectives if d.rank_conditional]
    assert flagged, "collective under a rank-varying trip count not flagged"
    assert [f for f in check_rank_invariance(program) if f.severity == "error"]


def test_psum_laundering_clears_taint(group):
    """A predicate *derived from* axis_index but passed through psum is
    rank-uniform again (every rank holds the identical sum) — branching on
    it is legal and must not be flagged."""

    def body(x):
        r = jax.lax.axis_index("intra")
        uniform = jax.lax.psum(r, "intra")  # identical on every rank

        def exchange(v):
            return jax.lax.psum(v, "intra")

        def skip(v):
            return v * 4.0

        return jax.lax.cond(uniform > 0, exchange, skip, x)

    fn = group.shard_map(body, in_specs=(P("intra"),), out_specs=P("intra"))
    x = jnp.ones((8, 4), jnp.float32)
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))
    assert not [
        f for f in check_rank_invariance(program) if f.severity == "error"
    ]


# ---------------------------------------------------------------------------
# Adversarial program 2: bucket wire bytes off by one from the planner model
# ---------------------------------------------------------------------------


def test_bucket_bytes_off_by_one_rejected(group):
    """Tamper the planner's view of bucket 0 by a single element: the IR's
    observed ring bytes no longer equal the analytic model and
    check_wire_exactness must reject, naming the bucket's exchange label.
    (flat fuse, so the payload model reads ``spec.numel`` directly.)"""
    ddp = make_ddp(group, GradientAllReduceAlgorithm(fuse="flat"))
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        cfg = WireModelConfig.from_engine(ddp)
        program, _ = collect_ir(
            ddp._build_sharded("default"),
            (
                jax.eval_shape(lambda s: s, state),
                jax.eval_shape(lambda b: b, make_batch()),
            ),
            dict(group.mesh.shape),
        )
        # control: the honest plan verifies byte-exact
        clean, _ = check_wire_exactness(program, cfg)
        assert not [f for f in clean if f.severity == "error"]

        specs = list(cfg.plan.specs)
        specs[0] = dataclasses.replace(specs[0], numel=specs[0].numel + 1)
        tampered = dataclasses.replace(
            cfg, plan=SimpleNamespace(specs=tuple(specs))
        )
        findings, _ = check_wire_exactness(program, tampered)
        errors = [f for f in findings if f.severity == "error"]
        assert errors, "off-by-one bucket bytes were not rejected"
        assert all(f.check == "wire_exactness" for f in errors)
        assert any(f.bucket == 0 for f in errors)
        assert any("bucket=0" in f.label for f in errors if f.label)
    finally:
        ddp.shutdown()


def test_cond_sibling_branches_not_double_counted(group):
    """The walker records every branch of a cond but only one executes:
    the wire census must charge sibling branches of the same cond the max,
    not the sum (a scope duplicated across both branches used to produce a
    false wire_exactness error)."""
    from types import SimpleNamespace as NS

    from bagua_tpu.analysis.collective_ir import (
        CollectiveDescriptor, CollectiveProgram,
    )

    def desc(i, path, wire):
        return CollectiveDescriptor(
            index=i, primitive="psum", reduce_op="sum", axes=("intra",),
            ring_size=4, shapes=((8,),), dtypes=("float32",), nbytes=32,
            wire_bytes=wire, label=f"d{i}",
            scope={"algo": "toy", "bucket": 0, "phase": "mono"},
            mp=None, qr=None, path=path, rank_conditional=False,
            cond_label=None,
        )

    program = CollectiveProgram(
        collectives=[
            desc(0, (), 50),                 # outside any cond: always runs
            desc(1, ("cond#0@0",), 100),     # branch 0
            desc(2, ("cond#0@1",), 100),     # sibling branch: exclusive
            desc(3, ("cond#1@0",), 7),       # a second, independent cond
        ],
        axis_sizes={"intra": 4},
    )
    cfg = WireModelConfig(algo="other", plan=NS(specs=()), n=4)
    findings, table = check_wire_exactness(program, cfg)
    assert not [f for f in findings if f.severity == "error"]
    (row,) = table
    assert row["observed_bytes"] == 50 + 100 + 7, row

    # and a real trace assigns sibling branches of one cond distinct ids
    def body(x, step):
        def a(v):
            return jax.lax.psum(v, "intra")

        def b(v):
            return jax.lax.psum(v * 2.0, "intra")

        return jax.lax.cond(step % 2 == 0, a, b, x)

    fn = group.shard_map(
        body, in_specs=(P("intra"), P()), out_specs=P("intra")
    )
    traced, _ = collect_ir(
        fn, (jnp.ones((8, 4), jnp.float32), jnp.zeros((), jnp.int32)),
        dict(group.mesh.shape),
    )
    frames = [d.path[-1] for d in traced.collectives if d.path]
    cids = {f.partition("@")[0] for f in frames}
    branches = {f.partition("@")[2] for f in frames}
    assert len(cids) == 1, frames
    assert branches == {"0", "1"}, frames


# ---------------------------------------------------------------------------
# Adversarial program 3: stale exported plan version
# ---------------------------------------------------------------------------


def test_stale_plan_version_rejected(group):
    """A plan payload exported before the last rebucket (plan_version
    behind the engine's) must be rejected by check_plan_conformance."""
    ddp = make_ddp(group)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        stale = {"plan_version": ddp.plan_version + 1}
        report = verify_step_program(
            ddp, state, make_batch(), variant="default", payload=stale
        )
        assert not report.ok
        assert all(f.check == "plan_conformance" for f in report.errors)
        assert any("plan_version" in f.message for f in report.errors)
        with pytest.raises(StaticVerifyError, match="plan_conformance"):
            report.raise_if_failed()

        # control: the freshly exported version verifies clean
        ok = verify_step_program(
            ddp, state, make_batch(), variant="default",
            payload={"plan_version": ddp.plan_version},
        )
        assert ok.ok, ok.summary()
    finally:
        ddp.shutdown()


# ---------------------------------------------------------------------------
# The strict gate: rejected programs never dispatch
# ---------------------------------------------------------------------------


def test_strict_gate_blocks_dispatch(group, monkeypatch):
    """Under ``BAGUA_STATIC_VERIFY=strict`` a program failing verification
    raises before the jitted step ever runs: the flight recorder holds zero
    records and no flight program was finalized."""
    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    orig = WireModelConfig.from_engine.__func__

    def tampered(cls, ddp):
        cfg = orig(cls, ddp)
        specs = list(cfg.plan.specs)
        specs[0] = dataclasses.replace(specs[0], numel=specs[0].numel + 1)
        return dataclasses.replace(
            cfg, plan=SimpleNamespace(specs=tuple(specs))
        )

    monkeypatch.setattr(
        WireModelConfig, "from_engine", classmethod(tampered)
    )
    tel = Telemetry(flight=FlightRecorder(capacity=64, rank=0, world_size=1))
    ddp = make_ddp(group, GradientAllReduceAlgorithm(fuse="flat"),
                   telemetry=tel)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        with pytest.raises(StaticVerifyError, match="wire_exactness"):
            ddp.train_step(state, make_batch())
        assert tel.flight.records() == [], "collectives dispatched anyway"
        variant = ddp.impl.step_variant(0)
        assert ddp.flight_program(variant) is None
        # the rejected step must not linger in any cache: a caller that
        # catches the error and retries re-verifies instead of dispatching
        assert ddp.compiled_step(variant) is None, "rejected step left in the jit cache"
        assert ddp.predicted_program(variant) is None
        with pytest.raises(StaticVerifyError, match="wire_exactness"):
            ddp.train_step(state, make_batch())
        assert tel.flight.records() == []
    finally:
        ddp.shutdown()


def test_strict_gate_passes_real_engines(group, monkeypatch):
    """Strict mode on honest engines: the gate verifies on the first
    train_step (trace time), dispatch proceeds, and the live capture equals
    the stored prediction record-for-record."""
    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    for name in ("gradient_allreduce", "zero"):
        tel = Telemetry(
            flight=FlightRecorder(capacity=128, rank=0, world_size=1)
        )
        ddp = make_ddp(group, build_algorithm(name, lr=0.1), telemetry=tel)
        try:
            state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
            state, losses = ddp.train_step(state, make_batch())
            jax.block_until_ready(losses)
            variant = ddp.impl.step_variant(0)
            predicted = ddp.predicted_program(variant)
            captured = ddp.flight_program(variant)
            assert predicted, f"{name}: gate stored no prediction"
            assert captured, f"{name}: no live flight program"
            assert canonical_records(predicted) == canonical_records(captured)
        finally:
            ddp.shutdown()


def test_warn_gate_logs_but_dispatches(group, monkeypatch, caplog):
    """``warn`` mode: same tampered engine as the strict test, but the step
    must run — findings land in the log instead of an exception."""
    import logging

    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "warn")
    orig = WireModelConfig.from_engine.__func__

    def tampered(cls, ddp):
        cfg = orig(cls, ddp)
        specs = list(cfg.plan.specs)
        specs[0] = dataclasses.replace(specs[0], numel=specs[0].numel + 1)
        return dataclasses.replace(
            cfg, plan=SimpleNamespace(specs=tuple(specs))
        )

    monkeypatch.setattr(
        WireModelConfig, "from_engine", classmethod(tampered)
    )
    ddp = make_ddp(group, GradientAllReduceAlgorithm(fuse="flat"))
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        with caplog.at_level(logging.WARNING, logger="bagua_tpu.ddp"):
            state, losses = ddp.train_step(state, make_batch())
        jax.block_until_ready(losses)
        assert any("wire_exactness" in r.message for r in caplog.records)
    finally:
        ddp.shutdown()


# ---------------------------------------------------------------------------
# The bounded-staleness sanction
# ---------------------------------------------------------------------------


def _stale_cond_program(group, mark=True, equal_bytes=True, both_exchange=True):
    """Hand-rolled bounded-staleness shape: a *rank-conditional* cond whose
    branches differ in payload.  Knobs degrade it into the rejectable
    variants: drop the scope marker, shrink one branch's wire bytes, or
    skip the exchange in one branch entirely."""
    from contextlib import nullcontext

    from bagua_tpu.observability.scope_grammar import format_stale_scope

    scope = (lambda: jax.named_scope(format_stale_scope(2))) if mark \
        else nullcontext

    def body(x):
        r = jax.lax.axis_index("intra")

        def fresh(v):
            with scope():
                return jax.lax.psum(v, "intra")

        def replay(v):
            if not both_exchange:
                return v * 2.0
            if not equal_bytes:
                with scope():
                    half = jax.lax.psum(v[:, :2], "intra")
                return jnp.concatenate([half, v[:, 2:]], axis=1)
            with scope():
                return jax.lax.psum(v * 0.5, "intra")

        return jax.lax.cond(r == 0, fresh, replay, x)

    fn = group.shard_map(body, in_specs=(P("intra"),), out_specs=P("intra"))
    x = jnp.ones((8, 4), jnp.float32)
    program, _ = collect_ir(fn, (x,), dict(group.mesh.shape))
    return program


def test_stale_marker_with_equal_bytes_is_sanctioned_info(group):
    """The sanctioned exception: rank-conditional cond, BOTH branches under
    the ``bagua_stale/tau=<k>`` marker moving identical wire bytes — the
    wire census is preserved either way the predicate falls, so the finding
    downgrades to info and strict verification would pass."""
    program = _stale_cond_program(group)
    flagged = [d for d in program.collectives if d.rank_conditional]
    assert flagged and all(d.stale == 2 for d in flagged)
    findings = check_rank_invariance(program)
    assert not [f for f in findings if f.severity == "error"], findings
    infos = [f for f in findings if f.severity == "info"]
    assert infos and all("sanctioned" in f.message for f in infos)
    assert any("tau=2" in f.message for f in infos)


def test_stale_marker_with_unequal_bytes_is_rejected(group):
    """Marker present but the branches move different wire bytes: the
    staleness sanction must NOT launder a genuine census divergence."""
    program = _stale_cond_program(group, equal_bytes=False)
    errors = [
        f for f in check_rank_invariance(program) if f.severity == "error"
    ]
    assert errors, "unequal-byte staleness cond was sanctioned"


def test_stale_marker_single_branch_exchange_is_rejected(group):
    """Marker present but only one branch exchanges at all: ranks could skip
    the collective outright — never sanctionable."""
    program = _stale_cond_program(group, both_exchange=False)
    errors = [
        f for f in check_rank_invariance(program) if f.severity == "error"
    ]
    assert errors, "single-branch staleness cond was sanctioned"


def test_unmarked_equal_bytes_cond_is_still_rejected(group):
    """Equal bytes alone don't earn the sanction — the descriptor must opt
    in with the scope marker, otherwise the program is presumed buggy."""
    program = _stale_cond_program(group, mark=False)
    assert all(d.stale is None for d in program.collectives)
    errors = [
        f for f in check_rank_invariance(program) if f.severity == "error"
    ]
    assert errors, "unmarked rank-conditional cond was sanctioned"


def test_strict_gate_passes_bounded_staleness_engines(group, monkeypatch):
    """The real relaxations under the strict gate: stale τ=2 (directive up)
    and gossip decentralized τ=2 verify and dispatch — their where-gated
    payloads never introduce rank-conditional control flow — and a τ
    switch re-verifies before the re-bounded step dispatches."""
    import optax

    from bagua_tpu.algorithms.decentralized import DecentralizedAlgorithm
    from bagua_tpu.algorithms.stale import StaleSyncAlgorithm

    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    for algo in (
        StaleSyncAlgorithm(staleness_tau=2),
        DecentralizedAlgorithm(hierarchical=False, staleness_tau=2),
    ):
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.1), algo,
            process_group=group, bucket_size_bytes=1 << 12,
        )
        try:
            state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
            state = ddp.apply_degradation_directive(state, (2,))
            state, losses = ddp.train_step(state, make_batch())
            jax.block_until_ready(losses)
            assert ddp.apply_staleness(1, reason="planner") is True
            state, losses = ddp.train_step(state, make_batch())
            jax.block_until_ready(losses)
        finally:
            ddp.shutdown()


# ---------------------------------------------------------------------------
# Re-verification on plan adoption
# ---------------------------------------------------------------------------


def test_rebucket_reverifies_and_rolls_back(group, monkeypatch):
    """After the gate has seen a batch, a rebucket re-verifies the new plan
    under strict mode; a verifier rejection rolls the old plan back."""
    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    ddp = make_ddp(group)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        state, _ = ddp.train_step(state, make_batch())
        old_plan, old_version = ddp.plan, ddp.plan_version
        plan2 = ddp.impl.tensors_to_buckets(
            ddp._tree_template, 1 << 14, filter_fn=None
        )
        ddp.rebucket(plan2)  # honest plan: re-verify passes
        assert ddp.plan_version > old_version

        # now make the verifier reject everything and attempt another
        # rebucket: the engine must roll back to the adopted plan
        adopted = ddp.plan
        from bagua_tpu import analysis

        def failing_verify(*a, **kw):
            raise StaticVerifyError([])

        monkeypatch.setattr(analysis, "verify_step_program", failing_verify)
        with pytest.raises(StaticVerifyError):
            ddp.rebucket(old_plan)
        assert ddp.plan is adopted, "rejected plan was not rolled back"
    finally:
        ddp.shutdown()


def test_gate_verifies_post_reshard_layout(group, monkeypatch):
    """With a sharded updater, the first cache-miss step after rebucket()
    carries a *pending host-side reshard*: the live state still has the old
    shard layout while the new program expects the new one.  The gate must
    trace over the post-reshard template — feeding the old-layout state
    into make_jaxpr verifies a program other than the one that dispatches
    (and crashes outright when the shapes disagree)."""
    from bagua_tpu import analysis

    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    verified_states = []
    orig = analysis.verify_step_program

    def spy(ddp_, state_, batch_, **kw):
        verified_states.append(state_)
        return orig(ddp_, state_, batch_, **kw)

    monkeypatch.setattr(analysis, "verify_step_program", spy)
    ddp = make_ddp(group, build_algorithm("zero", lr=0.1))
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        assert ddp._sharded_updater is not None
        state, _ = ddp.train_step(state, make_batch())
        plan2 = ddp.impl.tensors_to_buckets(
            ddp._tree_template, 1 << 14, filter_fn=None
        )
        ddp.rebucket(plan2)
        assert ddp._pending_reshard is not None
        # cache-miss step under the pending reshard: gate + dispatch OK,
        # and the gate traced the CURRENT layout's template, not the
        # stale live state
        state, losses = ddp.train_step(state, make_batch())
        jax.block_until_ready(losses)
        shapes = lambda t: jax.tree.map(lambda l: tuple(l.shape), t)
        assert shapes(verified_states[-1]) == shapes(ddp.state_template())
        # the gate handed the verifier the abstract CURRENT-layout template,
        # not the stale live state (whose shard layout predates the plan —
        # shapes can coincide between layouts, identity cannot)
        assert all(
            isinstance(l, jax.ShapeDtypeStruct)
            for l in jax.tree_util.tree_leaves(verified_states[-1])
        ), "gate traced the stale pre-reshard state"
    finally:
        ddp.shutdown()


def test_warn_gate_survives_trace_failure(group, monkeypatch, caplog):
    """A raw exception out of the verifier's trace (not a checker Finding)
    must not crash train_step in warn mode — logged, gate skipped, step
    dispatched.  Strict still propagates it."""
    import logging

    from bagua_tpu import analysis

    def boom(*a, **kw):
        raise TypeError("synthetic trace failure")

    monkeypatch.setattr(analysis, "verify_step_program", boom)

    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "warn")
    ddp = make_ddp(group)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        with caplog.at_level(logging.WARNING, logger="bagua_tpu.ddp"):
            state, losses = ddp.train_step(state, make_batch())
        jax.block_until_ready(losses)
        assert any("trace failed" in r.message for r in caplog.records)
    finally:
        ddp.shutdown()

    monkeypatch.setenv("BAGUA_STATIC_VERIFY", "strict")
    ddp = make_ddp(group)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        with pytest.raises(TypeError, match="synthetic trace failure"):
            ddp.train_step(state, make_batch())
        assert ddp.compiled_step(ddp.impl.step_variant(0)) is None
    finally:
        ddp.shutdown()


# ---------------------------------------------------------------------------
# CI surfaces: the sweep artifact, the lint, the hang analyzer's strict exit
# ---------------------------------------------------------------------------


def test_static_verify_json_committed_and_green():
    """The committed sweep artifact must exist, be green, and cover every
    registered algorithm x {f32,int8,int4} x {overlap off,on}."""
    path = os.path.join(REPO, "STATIC_VERIFY.json")
    assert os.path.exists(path), "STATIC_VERIFY.json not committed"
    with open(path) as f:
        report = json.load(f)
    assert report["summary"]["fail"] == 0
    assert report["summary"]["live_mismatch"] == 0
    assert report["summary"]["pass"] > 0
    from bagua_tpu.algorithms import GlobalAlgorithmRegistry

    cells = {(r["algo"], r["wire"], r["overlap"]) for r in report["rows"]}
    for name in GlobalAlgorithmRegistry.keys():
        for wire in ("f32", "int8", "int4"):
            for overlap in (False, True):
                assert (name, wire, overlap) in cells, (name, wire, overlap)
    live = {r["algo"]: r for r in report["live_capture"]}
    assert set(live) == {"gradient_allreduce", "zero"}
    assert all(r["match"] for r in live.values())


@pytest.mark.slow
def test_lint_traced_detects_planted_hazards(tmp_path):
    """The retrace lint flags all four hazard classes in a planted file and
    exits nonzero on non-baselined findings."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time, random\n"
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t = time.time()\n"
        "    r = random.random()\n"
        "    if jnp.any(x > 0):\n"
        "        x = x + 1\n"
        "    return x, t, r, int(jnp.sum(x))\n"
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "ci", "lint_traced.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    for rule in ("wallclock-in-traced", "host-random-in-traced",
                 "python-if-on-traced-call", "concretize-traced"):
        assert rule in proc.stdout, f"{rule} not detected:\n{proc.stdout}"


@pytest.mark.slow
def test_lint_traced_repo_is_baselined():
    """The repo itself lints clean against the committed baseline."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "ci", "lint_traced.py")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"


@pytest.mark.slow
def test_diagnose_hang_strict_exits_nonzero_on_desync(tmp_path):
    """``ci/diagnose_hang.py --strict`` returns 4 on a desync verdict and 0
    on a healthy gang."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_flight_recorder import rank_dump

    for r in range(4):
        rank_dump(tmp_path, r, 12, drop_idx=7 if r == 2 else None)
    script = os.path.join(REPO, "ci", "diagnose_hang.py")
    proc = subprocess.run(
        [sys.executable, script, "--dir", str(tmp_path), "--strict"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 4, proc.stderr
    assert "desync" in proc.stderr

    healthy = tmp_path / "healthy"
    healthy.mkdir()
    for r in range(4):
        rank_dump(healthy, r, 12)
    proc = subprocess.run(
        [sys.executable, script, "--dir", str(healthy), "--strict"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
