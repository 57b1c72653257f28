"""``wire_precision``: the in-collective quantized-ring exchange wired into
the gradient-allreduce and zero engines — int8/int4 training behavior, int4
error-feedback state, the "auto" + per-bucket precision plan path, and the
modelled per-precision wire-byte accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.kernels.quantized_ring import ring_wire_bytes
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.sharded import ZeroAlgorithm

N = 8
LAYERS = [10, 16, 4]  # 244 params; 1<<9 bucket bytes -> 3 buckets, last padded
STEPS = 5


def _batches(steps=STEPS, seed=1):
    rng = np.random.RandomState(seed)
    return [
        (jnp.asarray(rng.randn(16, LAYERS[0]), np.float32),
         jnp.asarray(rng.randn(16, LAYERS[-1]), np.float32))
        for _ in range(steps)
    ]


def _run(group, algo, overlap=False, steps=STEPS, precision_plan=None):
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(5e-2), algo, process_group=group,
        bucket_size_bytes=1 << 9, overlap=overlap,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    if precision_plan is not None:
        assert ddp.apply_precision_plan(precision_plan)
    losses = []
    for b in _batches(steps):
        state, loss = ddp.train_step(state, b)
        losses.append(float(np.asarray(loss)[0]))
    return ddp, state, losses


def _params0(state):
    return jax.tree.map(lambda l: np.asarray(l)[0], state.params)


def _assert_ranks_synced(state):
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray, state.params)):
        for r in range(1, N):
            np.testing.assert_array_equal(leaf[0], leaf[r])


# -- gradient_allreduce ------------------------------------------------------


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_allreduce_quantized_trains_and_syncs(group, precision):
    """Quantized-wire training converges on the fixture model, keeps every
    rank bitwise-synchronized (the ring output is identical everywhere), and
    stays close to the exact-f32 trajectory."""
    _, ref_state, ref_losses = _run(group, GradientAllReduceAlgorithm())
    _, state, losses = _run(
        group, GradientAllReduceAlgorithm(wire_precision=precision)
    )
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)
    _assert_ranks_synced(state)
    # few-step drift vs f32 is bounded by the quantization granularity
    atol = 5e-3 if precision == "int8" else 5e-2
    for a, b in zip(jax.tree.leaves(_params0(state)), jax.tree.leaves(_params0(ref_state))):
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)


def test_allreduce_int8_deterministic(group):
    """Two identical int8 runs are bitwise-identical — the quantized ring is
    a deterministic program, not a stochastic compressor."""
    _, s1, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int8"))
    _, s2, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int8"))
    for a, b in zip(jax.tree.leaves(_params0(s1)), jax.tree.leaves(_params0(s2))):
        np.testing.assert_array_equal(a, b)


def test_allreduce_int8_overlap_bitwise_matches_mono(group):
    """int8 is stateless, so the per-bucket overlap exchange runs the exact
    same ring program as the monolithic path — bitwise."""
    _, mono, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int8"),
                      overlap=False)
    _, over, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int8"),
                      overlap=True)
    for a, b in zip(jax.tree.leaves(_params0(mono)), jax.tree.leaves(_params0(over))):
        np.testing.assert_array_equal(a, b)


def test_allreduce_int4_carries_error_feedback_state(group):
    """int4 allocates one f32 residual per bucket, and after a step the
    residuals are non-zero (16 levels always leave requantization error on a
    real gradient)."""
    ddp, state, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int4"),
                         steps=2)
    resid = state.algo_state["qr_residual"]
    assert len(resid) == ddp.plan.num_buckets
    for r, spec in zip(resid, ddp.plan.specs):
        assert r.shape == (N, spec.numel) and r.dtype == jnp.float32
    assert any(float(jnp.max(jnp.abs(r))) > 0 for r in resid)


def test_allreduce_int4_error_feedback_beats_plain_requant(group):
    """The EF residual re-enters the next step's gradient: over a longer run
    the int4 trajectory tracks f32 more closely than the worst-case one-shot
    quantization error would suggest — concretely, the final loss lands
    within 10% of the exact run's."""
    _, _, ref_losses = _run(group, GradientAllReduceAlgorithm(), steps=12)
    _, _, q_losses = _run(
        group, GradientAllReduceAlgorithm(wire_precision="int4"), steps=12
    )
    assert q_losses[-1] < q_losses[0]
    assert q_losses[-1] <= ref_losses[-1] * 1.10, (q_losses[-1], ref_losses[-1])


def test_allreduce_int4_fences_overlap_and_rebucket(group):
    from bagua_tpu.bucket import BucketPlan

    algo = GradientAllReduceAlgorithm(wire_precision="int4")
    with pytest.raises(ValueError, match="per-bucket state"):
        DistributedDataParallel(
            mse_loss, optax.sgd(5e-2), algo, process_group=group, overlap=True
        )
    ddp, _, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="int4"),
                     steps=1)
    with pytest.raises(ValueError, match="per-bucket state"):
        ddp.rebucket(BucketPlan.from_tree(
            init_mlp(jax.random.PRNGKey(0), LAYERS),
            bucket_size_bytes=1 << 22, align_elems=group.size,
        ))


def test_allreduce_hierarchical_int8_trains(group):
    """hierarchical + quantized: exact f32 sum intra-node, quantized ring on
    the inter leg only — still converges and stays rank-synchronized."""
    _, state, losses = _run(
        group, GradientAllReduceAlgorithm(hierarchical=True, wire_precision="int8")
    )
    assert losses[-1] < losses[0], losses
    _assert_ranks_synced(state)


def test_auto_without_plan_is_bitwise_f32(group):
    """wire_precision="auto" never quantizes until a plan is adopted — the
    trajectory is bitwise the plain engine's."""
    _, ref, _ = _run(group, GradientAllReduceAlgorithm())
    _, auto, _ = _run(group, GradientAllReduceAlgorithm(wire_precision="auto"))
    for a, b in zip(jax.tree.leaves(_params0(auto)), jax.tree.leaves(_params0(ref))):
        np.testing.assert_array_equal(a, b)


def test_auto_mixed_precision_plan(group):
    """A planner-style mixed plan (one bucket per precision) trains, keeps
    ranks synced, and resolves exactly as adopted."""
    ddp, state, losses = _run(
        group, GradientAllReduceAlgorithm(wire_precision="auto"),
        precision_plan=["int8", "f32", "int4"],
    )
    assert ddp.impl.bucket_precisions(ddp.plan) == ["int8", "f32", "int4"]
    assert losses[-1] < losses[0], losses
    _assert_ranks_synced(state)
    # re-applying the same plan is a no-op (keeps the compiled step)
    fn = ddp.compiled_step()
    assert fn is not None
    assert not ddp.apply_precision_plan(["int8", "f32", "int4"])
    assert ddp.compiled_step() is fn


def test_precision_plan_validation(group):
    impl = GradientAllReduceAlgorithm(wire_precision="int8").reify(group)
    with pytest.raises(ValueError, match="auto"):
        impl.set_bucket_precision(["int8"])
    impl = GradientAllReduceAlgorithm(wire_precision="auto").reify(group)
    with pytest.raises(ValueError, match="unknown wire precisions"):
        impl.set_bucket_precision(["bf16"])
    with pytest.raises(ValueError, match="wire_precision must be one of"):
        GradientAllReduceAlgorithm(wire_precision="fp8").reify(group)


# -- zero --------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_zero_quantized_trains_and_syncs(group, precision):
    """The zero engine's gradient leg rides the quantized reduce-scatter;
    the deferred parameter all-gather stays f32, so ranks remain bitwise in
    sync after the swap-in."""
    _, state, losses = _run(
        group, ZeroAlgorithm(wire_precision=precision),
        overlap=(precision == "int8"),
    )
    assert losses[-1] < losses[0], losses
    _assert_ranks_synced(state)


def test_zero_int4_error_feedback_state(group):
    ddp, state, _ = _run(group, ZeroAlgorithm(wire_precision="int4"), steps=2)
    assert "qr_residual" in state.algo_state
    resid = state.algo_state["qr_residual"]
    assert len(resid) == ddp.plan.num_buckets
    assert any(float(jnp.max(jnp.abs(r))) > 0 for r in resid)


def test_zero_compression_exclusive_with_precision(group):
    with pytest.raises(ValueError, match="mutually exclusive"):
        ZeroAlgorithm(compression="bytegrad", wire_precision="int8").reify(group)


# -- wire-byte accounting ----------------------------------------------------


def test_wire_bytes_by_precision_accounting(group):
    """The modelled counters split by resolved precision and price quantized
    buckets from ring_wire_bytes (compressed payload + sidecar per hop)."""
    ddp, _, _ = _run(
        group, GradientAllReduceAlgorithm(wire_precision="auto"), steps=1,
        precision_plan=["int8", "f32", "int4"],
    )
    by_prec = ddp.impl.wire_bytes_by_precision(ddp.plan)
    specs = ddp.plan.specs
    assert by_prec["int8"] == ring_wire_bytes(specs[0].numel, N, 8)
    assert by_prec["f32"] == 2 * specs[1].nbytes * (N - 1) // N
    assert by_prec["int4"] == ring_wire_bytes(specs[2].numel, N, 4)


def test_quantized_step_compiles_once(group):
    """The quantized path keeps the recompile-free contract: one jit-cache
    miss for the whole run."""
    from bagua_tpu.observability.telemetry import Telemetry

    tel = Telemetry()
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(5e-2),
        GradientAllReduceAlgorithm(wire_precision="int8"),
        process_group=group, bucket_size_bytes=1 << 9, telemetry=tel,
    )
    state = ddp.init(init_mlp(jax.random.PRNGKey(0), LAYERS))
    for b in _batches(4):
        state, _ = ddp.train_step(state, b)
    assert sum(tel.recompile.compiles_by_variant.values()) == 1
