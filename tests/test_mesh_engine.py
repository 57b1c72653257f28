"""Named-mesh engine: MeshSpec construction/validation, axis-kwarg typo
fences on the engine and Trainer, dp x 1 bitwise parity with the legacy 1-D
engine, 2-D end-to-end training, and the per-axis static-verifier arms.

The tentpole's contract in one file: a ``MeshSpec`` threads named axes
through the group and the engine so the bucketed gradient exchange rides
the *data* axes only, while model axes (tp/fsdp-as-param-shard) keep their
own collectives — and every way to get that wiring wrong (typo'd axis
kwarg, role mismatch, hierarchical algorithm on a named mesh, an exchange
collective leaking onto a model axis) fails loudly at construction or
static-verify time instead of silently averaging across tensor-parallel
shards.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.analysis import (
    WireModelConfig,
    check_plan_conformance,
    collect_ir,
    verify_step_program,
)
from bagua_tpu.analysis.verify import _abstract
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.mesh import DATA_AXIS_NAMES, MODEL_AXIS_NAMES, MeshSpec
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import FlightRecorder, Telemetry
from bagua_tpu.sharded.algorithm import ZeroAlgorithm
from bagua_tpu.trainer import Trainer

LAYERS = [12, 16, 16, 4]


def make_batch(seed=0, n=32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, LAYERS[0]).astype(np.float32))
    y = jnp.asarray(rng.randn(n, LAYERS[-1]).astype(np.float32))
    return x, y


def make_ddp(group, algo=None, **kw):
    kw.setdefault("bucket_size_bytes", 1 << 9)
    return DistributedDataParallel(
        mse_loss, optax.sgd(0.1), algo or GradientAllReduceAlgorithm(),
        process_group=group, **kw,
    )


# -- MeshSpec construction and validation (satellite 1) -----------------------


def test_meshspec_roles_and_sizes():
    spec = MeshSpec({"dp": 4, "tp": 2})
    assert spec.names == ("dp", "tp")
    assert spec.size == 8 and spec.shape == (4, 2)
    assert spec.data_axes == ("dp",) and spec.model_axes == ("tp",)
    assert spec.exchange_size == 4
    assert "dp" in DATA_AXIS_NAMES and "tp" in MODEL_AXIS_NAMES

    spec = MeshSpec({"dp": 4, "fsdp": 2})
    assert spec.data_axes == ("dp", "fsdp")
    assert spec.exchange_size == 8  # fsdp rides the exchange too

    # explicit overrides beat name inference
    spec = MeshSpec({"rows": 4, "cols": 2}, dp_axis="rows", tp_axis="cols")
    assert spec.data_axes == ("rows",) and spec.model_axes == ("cols",)


def test_meshspec_equality_and_repr():
    a, b = MeshSpec({"dp": 4, "tp": 2}), MeshSpec({"dp": 4, "tp": 2})
    assert a == b and hash(a) == hash(b)
    assert a != MeshSpec({"dp": 2, "tp": 4})
    assert "dp=4" in repr(a) and "tp=2" in repr(a)


def test_meshspec_typo_axis_kwarg_raises():
    """A typo'd dp_axis/tp_axis/fsdp_axis names none of the declared axes —
    the construction-time fence for the silent-replication failure mode."""
    with pytest.raises(ValueError, match="none of the declared mesh axes"):
        MeshSpec({"dp": 4, "tp": 2}, dp_axis="dpp")
    with pytest.raises(ValueError, match="check the tp_axis spelling"):
        MeshSpec({"dp": 4, "tp": 2}, tp_axis="pt")


def test_meshspec_malformed_specs_raise():
    with pytest.raises(ValueError, match="at least one axis"):
        MeshSpec({})
    with pytest.raises(ValueError, match="duplicate mesh axis names"):
        MeshSpec([("dp", 4), ("dp", 2)])
    with pytest.raises(ValueError, match="non-positive size"):
        MeshSpec({"dp": 0})
    with pytest.raises(ValueError, match="exactly one role"):
        MeshSpec({"dp": 4, "tp": 2}, dp_axis="tp", tp_axis="tp")
    with pytest.raises(ValueError, match="no inferable role"):
        MeshSpec({"rows": 4, "cols": 2})
    with pytest.raises(ValueError, match="carry the data-parallel exchange"):
        MeshSpec({"tp": 8})


def test_group_needs_matching_device_count():
    with pytest.raises(ValueError, match="needs 16 devices"):
        bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 8, "tp": 2}))


def test_group_exposes_mesh_axes():
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    assert g.all_axes == ("dp", "tp")
    assert g.data_axes == ("dp",) and g.model_axes == ("tp",)
    assert g.size == 8 and g.exchange_size == 4
    assert dict(g.mesh.shape) == {"dp": 4, "tp": 2}


# -- engine / Trainer axis-kwarg fences (satellite 1) -------------------------


def test_ddp_typo_axis_kwarg_raises():
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    with pytest.raises(ValueError, match="none of the declared mesh axes"):
        make_ddp(g, dp_axis="ddp")
    with pytest.raises(ValueError, match="none of the declared mesh axes"):
        make_ddp(g, tp_axis="tpp")


def test_trainer_typo_axis_kwarg_raises():
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    with pytest.raises(ValueError, match="none of the declared mesh axes"):
        Trainer(
            mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
            process_group=g, dp_axis="ddp",
        )


def test_ddp_axis_role_mismatch_raises():
    """Naming a declared-but-wrong-role axis is a different bug than a typo
    and gets a different message: the axis exists, its role doesn't fit."""
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    with pytest.raises(ValueError, match="must name one of its data axes"):
        make_ddp(g, dp_axis="tp")
    with pytest.raises(ValueError, match="must name one of its model axes"):
        make_ddp(g, tp_axis="dp")


def test_hierarchical_fenced_on_named_mesh():
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    with pytest.raises(ValueError, match="legacy \\(inter, intra\\) mesh"):
        make_ddp(g, algo=GradientAllReduceAlgorithm(hierarchical=True))


# -- dp x 1 bitwise parity with the 1-D engine (acceptance) -------------------


@pytest.mark.parametrize("algo_cls", [GradientAllReduceAlgorithm, ZeroAlgorithm])
def test_dp1_bitwise_parity_with_legacy_engine(algo_cls):
    """A pure-dp MeshSpec mesh is the SAME machine as the legacy 1-D group:
    3 overlapped steps + finalize land bitwise-identical params AND
    optimizer state.  The refactor moved the axis wiring, not the math."""
    params = init_mlp(jax.random.PRNGKey(0), LAYERS)
    batches = [make_batch(seed=s) for s in range(3)]
    finals = []
    for spec in (None, MeshSpec({"dp": 8})):
        if spec is None:
            g = bagua_tpu.new_group(intra_size=1)
        else:
            g = bagua_tpu.new_group(mesh_spec=spec)
        ddp = make_ddp(g, algo=algo_cls(), overlap=True)
        state = ddp.init(params)
        for b in batches:
            state, losses = ddp.train_step(state, b)
        state = ddp.finalize_pending_updates(state)
        jax.block_until_ready(state)
        ddp.shutdown()
        finals.append(state)
    la, lb = jax.tree.leaves(finals[0]), jax.tree.leaves(finals[1])
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- 2-D meshes end-to-end (acceptance) ---------------------------------------


@pytest.mark.parametrize(
    "axes,algo_cls",
    [
        ({"dp": 4, "tp": 2}, GradientAllReduceAlgorithm),
        ({"dp": 4, "tp": 2}, ZeroAlgorithm),
        ({"dp": 4, "fsdp": 2}, GradientAllReduceAlgorithm),
        ({"dp": 4, "fsdp": 2}, ZeroAlgorithm),
    ],
)
def test_2d_mesh_trains_and_replicates(axes, algo_cls):
    """Both 2-D shapes train under both exchange algorithms with overlap on,
    and the final params are identical on every rank row — the dp average
    covers dp rows, and tp/fsdp peers ran the same replicated computation."""
    g = bagua_tpu.new_group(mesh_spec=MeshSpec(axes))
    ddp = make_ddp(g, algo=algo_cls(), overlap=True)
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    losses_seen = []
    for s in range(3):
        state, losses = ddp.train_step(state, make_batch(seed=s))
        losses_seen.append(float(np.asarray(losses).ravel()[0]))
    state = ddp.finalize_pending_updates(state)
    jax.block_until_ready(state)
    ddp.shutdown()
    assert all(np.isfinite(l) for l in losses_seen)
    for leaf in jax.tree.leaves(state.params):
        arr = np.asarray(leaf)
        assert arr.shape[0] == g.size
        for r in range(1, g.size):
            np.testing.assert_array_equal(arr[r], arr[0])


# -- static verifier on 2-D programs (acceptance) -----------------------------


@pytest.mark.parametrize(
    "axes,algo_cls,want_axes",
    [
        ({"dp": 4, "tp": 2}, GradientAllReduceAlgorithm, ("dp",)),
        ({"dp": 4, "fsdp": 2}, ZeroAlgorithm, ("dp", "fsdp")),
    ],
)
def test_static_verify_2d_program(axes, algo_cls, want_axes):
    g = bagua_tpu.new_group(mesh_spec=MeshSpec(axes))
    ddp = make_ddp(g, algo=algo_cls())
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    cfg = WireModelConfig.from_engine(ddp)
    assert cfg.exchange_axes == want_axes
    assert cfg.mesh_axes == tuple(axes)
    assert cfg.n == g.exchange_size
    report = verify_step_program(
        ddp, state, batch, variant=ddp.impl.step_variant(0)
    )
    errors = [f for f in report.findings if f.severity == "error"]
    assert report.ok, errors
    ddp.shutdown()


def test_axis_conformance_flags_stray_exchange_axis():
    """The negative arm: the same traced 2-D program fails conformance when
    the config claims the exchange is confined to an axis the collectives
    don't actually ride — the checker names the stray axes."""
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    ddp = make_ddp(g)
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    batch = make_batch()
    variant = ddp.impl.step_variant(0)
    program, _ = collect_ir(
        ddp._build_sharded(variant),
        (_abstract(state), _abstract(batch)),
        dict(g.mesh.shape),
    )
    cfg = WireModelConfig.from_engine(ddp)
    assert not [
        f for f in check_plan_conformance(program, cfg)
        if f.severity == "error"
    ]
    lying = dataclasses.replace(cfg, exchange_axes=("tp",))
    findings = [
        f for f in check_plan_conformance(program, lying)
        if f.severity == "error" and "stray" in f.message
    ]
    assert findings, "exchange collectives on dp were not flagged vs tp-only"
    assert any("'dp'" in f.message for f in findings)
    ddp.shutdown()


# -- flight records carry the exchange axes -----------------------------------


def test_flight_records_carry_data_axes():
    g = bagua_tpu.new_group(mesh_spec=MeshSpec({"dp": 4, "tp": 2}))
    fr = FlightRecorder(capacity=128, rank=0, world_size=1)
    ddp = make_ddp(g, telemetry=Telemetry(flight=fr))
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    state, losses = ddp.train_step(state, make_batch())
    jax.block_until_ready(losses)
    ddp.shutdown()
    program = ddp.flight_program()
    exchange = [r for r in program if r["phase"] != "hop"]
    assert exchange, "no exchange records captured"
    for rec in exchange:
        assert rec["axes"] == ["dp"]

# -- per-axis budget partition over engine-traced programs --------------------


@pytest.mark.parametrize(
    "axes", [{"dp": 8}, {"dp": 4, "tp": 2}, {"dp": 4, "fsdp": 2}],
    ids=["dp8", "dp4xtp2", "dp4xfsdp2"],
)
@pytest.mark.parametrize("algo_cls", [GradientAllReduceAlgorithm, ZeroAlgorithm])
@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_axis_budget_partition_exact_over_traced_program(
        axes, algo_cls, precision):
    """Property, over real traced programs (gar/zero x f32/int8 x three
    mesh shapes): the BudgetModel's per-axis wire ledger joined from the
    captured flight program covers exactly the mesh's data axes, its scalar
    wire promise is the ledger's sum, and the settled per-axis
    wire_slowdown split sums BITWISE to the scalar component on every
    pricing path — partition by construction, no tolerance."""
    from bagua_tpu.observability import BudgetModel
    from bagua_tpu.service.planner import AlphaBeta, CostModel

    g = bagua_tpu.new_group(mesh_spec=MeshSpec(axes))
    fr = FlightRecorder(capacity=256, rank=0, world_size=1)
    ddp = make_ddp(g, algo=algo_cls(wire_precision=precision),
                   telemetry=Telemetry(flight=fr))
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    state, losses = ddp.train_step(state, make_batch())
    jax.block_until_ready(losses)
    ddp.shutdown()
    program = ddp.flight_program()

    legs = {ax: AlphaBeta(0.0, 1e8 * (i + 1))
            for i, ax in enumerate(g.data_axes)}
    cm = CostModel(flat=AlphaBeta(0.0, 1e9), axis_legs=legs)
    model = BudgetModel(compute_ms=6.0, cost_model=cm, program=program)

    # the ledger joined from the program covers exactly the data axes the
    # exchange rides, and the scalar promise IS its sorted-key sum
    assert set(model.axis_wire_ms) == set(g.data_axes)
    assert all(v > 0 for v in model.axis_wire_ms.values())
    assert model.wire_ms == sum(
        model.axis_wire_ms[ax] for ax in sorted(model.axis_wire_ms))

    def assert_exact(budget):
        assert set(budget.wire_axis_ms) == set(g.data_axes)
        assert budget.components["wire_slowdown"] == sum(
            budget.wire_axis_ms[ax] for ax in sorted(budget.wire_axis_ms))
        assert budget.axis_partition_error_ms() == 0.0

    # path 1: per-axis measured wire (enqueue->retire deltas)
    model.note_wire(
        sum(model.axis_wire_ms.values()) * 2.0,
        by_axis={ax: ms * 2.0 for ax, ms in model.axis_wire_ms.items()})
    assert_exact(model.settle(0, 20.0))

    # path 2: scalar measured wire, split by the ledger's expected shares
    model.note_wire(model.wire_ms * 3.0)
    assert_exact(model.settle(1, 20.0))

    # path 3: per-axis byte census over the program's own traffic
    census = {ax: 0.0 for ax in g.data_axes}
    for rec in program:
        rec_axes = [a for a in (rec.get("axes") or ()) if a]
        if not rec_axes or not rec.get("nbytes"):
            continue
        for ax in rec_axes:
            census[ax] += float(rec["nbytes"]) / len(rec_axes)
    assert all(v > 0 for v in census.values())
    base = model.expected()  # clean steps must land inside the 25% band
    for step in range(2, 7):
        model.settle(step, base, wire_bytes_by_axis=dict(census))
    inflated = dict(census)
    worst = sorted(inflated)[-1]
    inflated[worst] *= 2.0
    assert_exact(model.settle(7, base + 4.0, wire_bytes_by_axis=inflated))
