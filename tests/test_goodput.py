"""Goodput / MFU accounting: analytic FLOPs, the wall-clock ledger, gauges.

Pins the acceptance criteria of the goodput meter:

* the analytic VGG16 estimator reproduces the perf-audit hand-math
  (``32 img × 46.5 GFLOP = 1.49 TF/step/chip``, compute floor 7.6 ms at
  100% MFU on a 197 TFLOP/s v5e) within 5%;
* the ledger's clocked buckets sum to the elapsed wall time — exactly under
  a fake clock, within 1% over a real engine run with a forced recompile
  and a blocking snapshot ride-along;
* compile wall lands in the ``compile_ms`` histogram, the recompile
  detector's ``compile_ms_total``, and the ledger's ``compile`` bucket;
* ``wire_efficiency`` divides the planner-predicted α–β wire time by the
  measured one.
"""

import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import (
    GoodputLedger,
    GoodputMeter,
    MetricsRegistry,
    Telemetry,
    flops_from_cost_analysis,
    model_flops_per_sample,
    predicted_wire_time,
    register_model_flops,
)
from bagua_tpu.observability.goodput import (
    LEDGER_BUCKETS,
    PEAK_FLOPS_PER_CHIP,
    TRAIN_FLOPS_MULTIPLIER,
    mlp_fwd_flops,
    vgg16_fwd_flops,
)

# the perf-audit hand-math constants (ci/perf_audit.py render_md)
AUDIT_VGG16_TRAIN_GFLOP = 46.5e9
AUDIT_V5E_PEAK = 197e12
V5E = "TPU v5 lite"  # the chip's device_kind: the peak table's key


# -- analytic estimators ------------------------------------------------------


def test_vgg16_flops_match_audit_hand_math():
    train = model_flops_per_sample("vgg16")
    assert train == pytest.approx(AUDIT_VGG16_TRAIN_GFLOP, rel=0.05)
    fwd = vgg16_fwd_flops()
    assert fwd * TRAIN_FLOPS_MULTIPLIER == train
    assert fwd == pytest.approx(15.5e9, rel=0.05)


def test_mfu_matches_audit_compute_floor():
    # audit: 32 img × 46.5 GFLOP = 1.49 TF/step/chip; 1.49/197 = 7.6 ms at
    # 100% MFU.  A step taking exactly the compute floor must report MFU≈1.
    reg = MetricsRegistry()
    meter = GoodputMeter(model="vgg16", peak_flops_per_chip=V5E, n_chips=1,
                         registry=reg)
    floor_s = 32 * AUDIT_VGG16_TRAIN_GFLOP / AUDIT_V5E_PEAK
    mfu = meter.on_step(wall_s=floor_s, n_samples=32)
    assert mfu == pytest.approx(1.0, rel=0.05)
    assert reg.snapshot()["mfu"] == pytest.approx(mfu, rel=1e-6)
    assert reg.snapshot()["model_flops_per_step"] == pytest.approx(
        32 * AUDIT_VGG16_TRAIN_GFLOP, rel=0.05)
    # half the throughput -> half the MFU; spread over 8 chips -> 1/8 each
    assert meter.on_step(wall_s=2 * floor_s, n_samples=32) == pytest.approx(
        mfu / 2, rel=1e-6)
    meter8 = GoodputMeter(model="vgg16", peak_flops_per_chip=V5E, n_chips=8)
    assert meter8.on_step(wall_s=floor_s, n_samples=32) == pytest.approx(
        mfu / 8, rel=1e-6)


def test_mlp_flops_and_registry():
    assert mlp_fwd_flops([64, 128, 4]) == 64 * 128 + 128 * 4
    assert model_flops_per_sample("mlp", sizes=[64, 128, 4]) == pytest.approx(
        3.0 * (64 * 128 + 128 * 4))
    assert model_flops_per_sample("mlp", train=False, sizes=[64, 128, 4]) == (
        64 * 128 + 128 * 4)
    with pytest.raises(KeyError):
        model_flops_per_sample("resnet9000")
    register_model_flops("toy", lambda width=2: 10.0 * width)
    assert model_flops_per_sample("toy", width=3) == pytest.approx(90.0)
    assert PEAK_FLOPS_PER_CHIP[V5E] == AUDIT_V5E_PEAK


def test_flops_from_cost_analysis_shapes():
    class C:
        def __init__(self, ca):
            self._ca = ca

        def cost_analysis(self):
            if isinstance(self._ca, Exception):
                raise self._ca
            return self._ca

    assert flops_from_cost_analysis(C({"flops": 123.0})) == 123.0
    assert flops_from_cost_analysis(C([{"flops": 7}])) == 7.0
    assert flops_from_cost_analysis(C({})) is None
    assert flops_from_cost_analysis(C({"flops": -1.0})) is None
    assert flops_from_cost_analysis(C({"flops": "n/a"})) is None
    assert flops_from_cost_analysis(C([])) is None
    assert flops_from_cost_analysis(C(RuntimeError("no backend"))) is None


def test_calibrate_from_compiled_adopts_xla_count():
    meter = GoodputMeter(flops_per_sample=1.0, peak_flops_per_chip=V5E)

    class C:
        def cost_analysis(self):
            return {"flops": 640.0}

    assert meter.calibrate_from_compiled(C(), n_samples=32) == pytest.approx(20.0)
    assert meter.flops_per_sample == pytest.approx(20.0)

    class N:
        def cost_analysis(self):
            return {}

    # nothing reported: keep the previous estimate
    assert meter.calibrate_from_compiled(N(), n_samples=32) is None
    assert meter.flops_per_sample == pytest.approx(20.0)


# -- the ledger ---------------------------------------------------------------


def test_ledger_partitions_wall_exactly_under_fake_clock():
    t = [100.0]
    led = GoodputLedger(clock=lambda: t[0])
    t[0] += 2.0          # 2 s startup
    led.enter("productive")
    t[0] += 5.0          # 5 s productive
    led.enter("data")
    t[0] += 1.0          # 1 s data
    led.enter("productive")
    t[0] += 4.0          # 4 s productive (1.5 of which was really a compile)
    led.reattribute("productive", "compile", 1.5)
    led.charge("lost_restart", 3.0)   # synthetic: previous incarnation's wall
    rep = led.report()
    b = rep["buckets"]
    assert b["startup"] == pytest.approx(2.0)
    assert b["productive"] == pytest.approx(7.5)
    assert b["data"] == pytest.approx(1.0)
    assert b["compile"] == pytest.approx(1.5)
    assert b["lost_restart"] == pytest.approx(3.0)
    assert rep["synthetic_s"] == pytest.approx(3.0)
    assert rep["wall_s"] == pytest.approx(12.0)
    # the identity: clocked buckets partition the wall exactly
    assert sum(b.values()) - rep["synthetic_s"] == pytest.approx(rep["wall_s"])
    assert rep["goodput_frac"] == pytest.approx(7.5 / 12.0)
    assert set(b) >= set(LEDGER_BUCKETS)


def test_ledger_reattribute_never_overdraws():
    t = [0.0]
    led = GoodputLedger(clock=lambda: t[0])
    led.enter("productive")
    t[0] += 1.0
    led.reattribute("productive", "compile", 99.0)  # capped at what's there
    rep = led.report()
    assert rep["buckets"]["productive"] == pytest.approx(0.0)
    assert rep["buckets"]["compile"] == pytest.approx(1.0)
    assert sum(rep["buckets"].values()) == pytest.approx(rep["wall_s"])


def test_on_restart_prices_lost_steps_at_p50():
    meter = GoodputMeter(flops_per_sample=1.0, peak_flops_per_chip=V5E)
    for w in (0.1, 0.2, 0.3, 0.2, 0.2):
        meter.on_step(wall_s=w, n_samples=1)
    meter.on_restart(lost_steps=4)
    rep = meter.ledger.report()
    assert rep["buckets"]["lost_restart"] == pytest.approx(4 * 0.2)
    assert rep["synthetic_s"] == pytest.approx(4 * 0.2)


# -- wire efficiency ----------------------------------------------------------


class FakeCostModel:
    def bucket_wire_time(self, nbytes, hierarchical=False, wire_pattern="allreduce"):
        return 1e-6 + nbytes / 1e9  # alpha + beta * bytes


def test_predicted_wire_time_and_efficiency_gauge():
    cm = FakeCostModel()
    buckets = [1 << 20, 1 << 20, 1 << 18]
    predicted = predicted_wire_time(cm, buckets)
    assert predicted == pytest.approx(sum(1e-6 + b / 1e9 for b in buckets))

    reg = MetricsRegistry()
    meter = GoodputMeter(flops_per_sample=1.0, peak_flops_per_chip=V5E, cost_model=cm,
                         bucket_bytes=buckets, registry=reg)
    assert meter.predicted_wire_s() == pytest.approx(predicted)
    eff = meter.observe_wire(measured_wire_s=2 * predicted)
    assert eff == pytest.approx(0.5)
    assert reg.snapshot()["wire_efficiency"] == pytest.approx(0.5, abs=1e-6)
    # no cost model -> no gauge, no crash
    bare = GoodputMeter(flops_per_sample=1.0, peak_flops_per_chip=V5E)
    assert bare.predicted_wire_s() is None
    assert bare.observe_wire(1.0) is None


# -- end-to-end: ledger over a real engine run --------------------------------


def test_ledger_sums_to_wall_over_real_run(group, tmp_path):
    """Acceptance: buckets sum to wall time ±1% over a run with a forced
    recompile and a blocking snapshot ride-along."""
    meter = GoodputMeter(model="mlp", model_kwargs={"sizes": [12, 16, 16, 4]},
                         peak_flops_per_chip=V5E, n_chips=8)
    tel = Telemetry(metrics_jsonl=str(tmp_path / "m.jsonl"), goodput=meter)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        process_group=group, bucket_size_bytes=1 << 9, overlap=True,
        telemetry=tel,
    )
    rng = np.random.RandomState(0)
    params = init_mlp(__import__("jax").random.PRNGKey(0), [12, 16, 16, 4])
    state = ddp.init(params)
    x = rng.randn(32, 12).astype(np.float32)
    y = rng.randn(32, 4).astype(np.float32)
    for _ in range(4):
        state, _ = ddp.train_step(state, (x, y))
    # forced recompile: new batch shape -> new jit variant
    x2 = rng.randn(16, 12).astype(np.float32)
    y2 = rng.randn(16, 4).astype(np.float32)
    state, _ = ddp.train_step(state, (x2, y2))
    # a blocking snapshot stalls the loop; the hub re-attributes its wall
    tel.on_snapshot(step=5, wall_ms=25.0, n_bytes=1 << 10, kind="forced")
    rep = meter.report()["ledger"]
    clocked = sum(rep["buckets"].values()) - rep["synthetic_s"]
    assert clocked == pytest.approx(rep["wall_s"], rel=0.01)
    # both compiles were re-attributed out of productive
    assert rep["buckets"]["compile"] > 0
    assert rep["buckets"]["snapshot"] >= 25e-3 * 0.9
    assert 0 < rep["goodput_frac"] < 1
    assert meter.last_mfu is not None and meter.last_mfu > 0
    ddp.shutdown()
    tel.close()


def test_compile_wall_lands_in_histogram_and_detector(group, tmp_path):
    meter = GoodputMeter(flops_per_sample=1.0, peak_flops_per_chip=V5E)
    tel = Telemetry(metrics_jsonl=str(tmp_path / "m.jsonl"), goodput=meter)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        process_group=group, bucket_size_bytes=1 << 9, overlap=False,
        telemetry=tel,
    )
    params = init_mlp(__import__("jax").random.PRNGKey(0), [12, 16, 16, 4])
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    batch = (rng.randn(32, 12).astype(np.float32),
             rng.randn(32, 4).astype(np.float32))
    for _ in range(3):
        state, _ = ddp.train_step(state, batch)
    snap = tel.registry.snapshot()
    assert snap["compile_ms"]["count"] == 1  # exactly the warmup compile
    rec = tel.recompile.report()
    assert rec["compile_ms_total"] > 0
    assert set(rec["compile_ms_by_variant"]) == set(rec["compiles_by_variant"])
    assert rec["compile_ms_total"] == pytest.approx(
        sum(rec["compile_ms_by_variant"].values()), rel=1e-6)
    ddp.shutdown()
    tel.close()
