"""Perf-lab unit gates: α–β fit round-trip, modeled-bytes == census-bytes
on the live engines, FLOP census exactness, Pallas evidence gating, and the
one-topology-model unification with ci/scaling_projection.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms import build_algorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.perflab import (
    DEFAULT_TOPOLOGY,
    flops_census,
    model_step_cell,
    pallas_kernel_basis,
    t_collective,
    torus_dims,
)
from bagua_tpu.service.planner import (
    AlphaBeta,
    CostModel,
    WireSample,
    fit_alpha_beta,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = [64, 128, 128, 64]


# ---------------------------------------------------------------------------
# α–β fit round-trip
# ---------------------------------------------------------------------------


def test_alpha_beta_fit_round_trip():
    """Samples synthesized from a known (α, β) fit back to it exactly —
    the cost model's seconds are then a faithful readback of the fixture."""
    truth = AlphaBeta(alpha=50e-6, beta=50e9)
    sizes = [1 << 16, 1 << 20, 1 << 24, 1 << 26]
    samples = [
        WireSample(nbytes=n, seconds=truth.predict(n), leg="flat")
        for n in sizes
    ]
    fit = fit_alpha_beta(samples, AlphaBeta(1e-3, 1e9))
    assert fit.alpha == pytest.approx(truth.alpha, rel=1e-6)
    assert fit.beta == pytest.approx(truth.beta, rel=1e-6)
    for n in sizes:
        assert fit.predict(n) == pytest.approx(truth.predict(n), rel=1e-9)


def test_cost_model_single_point_and_prior_degradation():
    """One operating point degrades gracefully (pure-bandwidth through the
    clamped α), and an unsampled leg falls back to its prior — both arms the
    BENCH_MODELED fit relies on with the single-sample vgg16 fixture."""
    one = [WireSample(nbytes=175_942_816, seconds=0.010842, leg="flat")]
    cm = CostModel.from_samples(one, intra_size=4)
    # the single-point fit must reproduce the observed point
    assert cm.flat.predict(one[0].nbytes) == pytest.approx(
        one[0].seconds, rel=1e-6
    )
    assert cm.flat.n_samples == 1
    # unsampled legs carry the planner priors (positive, finite)
    for leg in (cm.rs, cm.ag, cm.pp, cm.qr8, cm.qr4):
        assert leg.n_samples == 0
        assert leg.alpha > 0 and leg.beta > 0


# ---------------------------------------------------------------------------
# Modeled bytes == census bytes on the live engines
# ---------------------------------------------------------------------------


def _build(group, name, wire, overlap):
    kwargs = {} if wire == "f32" else {"wire_precision": wire}
    algo = build_algorithm(name, lr=0.1, **kwargs)
    return DistributedDataParallel(
        mse_loss, optax.sgd(0.1, momentum=0.9), algo,
        process_group=group, bucket_size_bytes=1 << 12, overlap=overlap,
    )


def _batch():
    rng = np.random.RandomState(0)
    return (
        jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32)),
        jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32)),
    )


@pytest.mark.parametrize("name,wire", [
    ("gradient_allreduce", "f32"),
    ("gradient_allreduce", "int8"),
    ("gradient_allreduce", "int4"),
    ("zero", "f32"),
    ("zero", "int8"),
    ("zero", "int4"),
])
def test_modeled_bytes_equal_census_bytes(group, name, wire):
    """The tentpole's provenance invariant, on the real traced engines: the
    bytes the α–β pricing charges are exactly the CollectiveIR census bytes
    (both branch-deduped the verifier's way), the cell verifies, and the
    modeled step is nonzero."""
    cost_model = CostModel.from_samples([], intra_size=4)
    ddp = _build(group, name, wire, overlap=False)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        cell = model_step_cell(ddp, state, _batch(), cost_model, wire=wire)
    finally:
        ddp.shutdown()
    assert cell.verified, cell.findings
    assert cell.modeled_wire_bytes == cell.census_wire_bytes
    assert cell.modeled_wire_bytes > 0
    assert cell.modeled_step_ms > 0
    assert cell.wire_ms > 0
    assert 0 < cell.modeled_goodput_frac <= 1.0
    # every priced group maps to a real cost-model leg
    assert cell.legs_used
    assert set(cell.legs_used) <= {
        "flat", "intra", "inter", "rs", "ag", "pp", "qr8", "qr4",
    }
    # and the leg breakdown re-sums to the totals
    assert sum(
        leg["wire_bytes"] for leg in cell.leg_breakdown.values()
    ) == cell.modeled_wire_bytes


def test_quantized_cells_ride_qr_legs(group):
    """int8/int4 wire programs must be priced on the quantized-ring legs —
    mispricing them as flat f32 exchanges would silently misrank the
    precision trade-off BENCH_MODELED exists to expose."""
    cost_model = CostModel.from_samples([], intra_size=4)
    for wire, leg in (("int8", "qr8"), ("int4", "qr4")):
        ddp = _build(group, "gradient_allreduce", wire, overlap=False)
        try:
            state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
            cell = model_step_cell(ddp, state, _batch(), cost_model, wire=wire)
        finally:
            ddp.shutdown()
        assert leg in cell.legs_used, (wire, cell.legs_used)
        assert cell.leg_breakdown[leg]["wire_bytes"] > 0


def test_census_matches_committed_artifact(group):
    """A fresh trace reproduces the committed BENCH_MODELED.json byte
    census for the headline cell — the committed artifact is live evidence,
    not a snapshot that can silently rot."""
    art = json.load(open(os.path.join(REPO, "BENCH_MODELED.json")))
    ref = next(
        r for r in art["rows"]
        if r["algo"] == "gradient_allreduce" and r["wire"] == "f32"
        and r["overlap"] is False
    )
    ddp = _build(group, "gradient_allreduce", "f32", overlap=False)
    try:
        state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
        cell = model_step_cell(
            ddp, state, _batch(), CostModel.from_samples([], intra_size=4)
        )
    finally:
        ddp.shutdown()
    assert cell.census_wire_bytes == ref["census_wire_bytes"]
    assert cell.num_collectives == ref["num_collectives"]


# ---------------------------------------------------------------------------
# FLOP census
# ---------------------------------------------------------------------------


def test_flops_census_counts_dot_general_exactly():
    a = jnp.zeros((32, 64), jnp.float32)
    b = jnp.zeros((64, 128), jnp.float32)
    closed = jax.make_jaxpr(lambda x, y: x @ y)(a, b)
    census = flops_census(closed)
    assert census["n_dots"] == 1
    assert census["flops"] == 2.0 * 32 * 64 * 128


def test_flops_census_cond_takes_max_branch():
    x = jnp.zeros((16, 16), jnp.float32)

    def f(p, x):
        return jax.lax.cond(p, lambda v: v @ v @ v, lambda v: v @ v, x)

    census = flops_census(jax.make_jaxpr(f)(True, x))
    # max branch: two matmuls, not three (2+1) summed across branches
    assert census["flops"] == 2 * (2.0 * 16 * 16 * 16)


# ---------------------------------------------------------------------------
# Pallas evidence gating
# ---------------------------------------------------------------------------


def test_pallas_basis_fallback_without_chip_evidence(tmp_path):
    # the committed PALLAS_TPU.json is interpret-mode CPU → fallback basis
    basis = pallas_kernel_basis("gradient_allreduce", "int8")
    assert basis["basis"] == "modeled-jnp-fallback"
    assert "quantized_ring_hop_int8" in basis["gated_kernels"]
    # f32 monolithic programs gate on no Pallas kernel at all
    assert pallas_kernel_basis("gradient_allreduce", "f32")["basis"] == (
        "jnp-native"
    )
    # real-chip evidence for every gated kernel flips the basis
    ev = tmp_path / "pallas.json"
    ev.write_text(json.dumps({
        "backend": "tpu v5e", "interpret": False,
        "kernels": [
            {"kernel": "quantized_ring_hop_int8"},
            {"kernel": "decompress_reduce_requantize"},
        ],
    }))
    chip = pallas_kernel_basis("gradient_allreduce", "int8",
                               evidence_path=str(ev))
    assert chip["basis"] == "measured-chip"


# ---------------------------------------------------------------------------
# One topology model (scaling_projection unification)
# ---------------------------------------------------------------------------


def test_topology_is_shared_with_scaling_projection():
    """Both committed artifacts carry the same TopologyAssumptions block —
    the 'two diverging cost models' failure mode is structurally gone."""
    desc = DEFAULT_TOPOLOGY.describe()
    sp = json.load(open(os.path.join(REPO, "SCALING_PROJECTION.json")))
    for key, val in desc.items():
        assert sp["assumptions"][key] == val, key
    bm = json.load(open(os.path.join(REPO, "BENCH_MODELED.json")))
    assert bm["assumptions"]["topology"] == desc


def test_t_collective_ring_model():
    topo = DEFAULT_TOPOLOGY
    n, B = 8, 1 << 20
    dx, dy = torus_dims(n)
    lat = (dx / 2 + dy / 2) * topo.ici_lat_hop
    assert t_collective("allreduce", B, n) == pytest.approx(
        2 * (n - 1) / n * B / topo.ici_bw_chip + 2 * lat
    )
    assert t_collective("allgather", B, n) == pytest.approx(
        (n - 1) / n * B / topo.ici_bw_chip + lat
    )
    assert t_collective("permute", B, n) == pytest.approx(
        B / topo.ici_bw_chip + topo.ici_lat_hop
    )
    assert t_collective("allreduce", B, 1) == 0.0
    # DCN leg parameters are explicit model fields, not buried constants
    assert topo.dcn_bw_chip() == topo.dcn_bw_host / topo.chips_per_host
