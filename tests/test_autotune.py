"""Autotune service tests (CPU-only tier, like reference ``tests/service``).

The main test mirrors the reference's ``MockBaguaProcess`` pattern
(``tests/service/test_autotune_service.py:29-102``): register fake tensor
declarations, report a synthetic concave score peaking at 20 MB buckets, and
assert the optimizer converges near the peak.
"""

import time

import numpy as np
import pytest

from bagua_tpu.defs import BaguaHyperparameter, TensorDeclaration
from bagua_tpu.service.autotune_client import AutotuneClient
from bagua_tpu.service.autotune_service import AutotuneService, start_autotune_server
from bagua_tpu.service.autotune_session import AutotuneSession, profile_bucket_order
from bagua_tpu.service.bayesian_optimizer import BayesianOptimizer, BoolParam, IntParam


def synthetic_score(bucket_size_bytes: int, hierarchical: bool) -> float:
    """Concave in log2(bucket size), peak at 2^21 * 10 ≈ 20 MB; hierarchy
    adds a small bonus (reference test peaks near 20MB too)."""
    p = np.log2(bucket_size_bytes)
    return float(100.0 - (p - np.log2(20 * 1024 ** 2)) ** 2 + (1.0 if hierarchical else 0.0))


def test_bayesian_optimizer_converges():
    opt = BayesianOptimizer(
        [IntParam("bucket_size_2p", 10, 31), BoolParam("is_hierarchical_reduce")],
        n_initial_points=5,
        seed=1,
    )
    for _ in range(40):
        params = opt.ask()
        score = synthetic_score(1 << params["bucket_size_2p"], bool(params["is_hierarchical_reduce"]))
        opt.tell(params, score)
    best, best_score = opt.best()
    # peak at log2(20 MiB) = 24.32
    assert abs(best["bucket_size_2p"] - 24.32) <= 1.5, best
    assert best["is_hierarchical_reduce"] == 1


def test_bayesian_optimizer_initial_walk_is_deterministic_and_duplicate_free():
    """The initial phase walks a seeded permutation: two optimizers with the
    same seed propose the same sequence, and no point is proposed twice —
    every duplicate would cost the client a re-jit it already paid for."""
    space = [IntParam("bucket_size_2p", 10, 31), BoolParam("is_hierarchical_reduce")]

    def walk(seed, n=8):
        opt = BayesianOptimizer(space, n_initial_points=n, seed=seed)
        seen = []
        for _ in range(n):
            p = opt.ask()
            seen.append(tuple(sorted(p.items())))
            opt.tell(p, 1.0)  # flat score: EI adds no signal
        return seen

    a, b = walk(seed=7), walk(seed=7)
    assert a == b, "same seed must give the same initial proposals"
    assert len(set(a)) == len(a), "initial walk re-proposed a point"
    assert walk(seed=8) != a, "different seeds should explore differently"


def test_bayesian_optimizer_ei_never_reproposes_explored_points():
    opt = BayesianOptimizer([IntParam("x", 0, 7)], n_initial_points=2, seed=0)
    seen = set()
    for _ in range(8):  # exhaust the whole 8-point grid
        p = opt.ask()
        assert p["x"] not in seen, "explored point re-proposed"
        seen.add(p["x"])
        opt.tell(p, float(p["x"]))
    assert seen == set(range(8))
    # everything explored: ask() must still answer (best-EI fallback)
    assert 0 <= opt.ask()["x"] <= 7


def test_bayesian_optimizer_warm_start_served_first():
    opt = BayesianOptimizer(
        [IntParam("bucket_size_2p", 10, 31), BoolParam("is_hierarchical_reduce")],
        n_initial_points=4, seed=0,
    )
    warm = [
        {"bucket_size_2p": 24, "is_hierarchical_reduce": 1},
        {"bucket_size_2p": 25, "is_hierarchical_reduce": 0},
    ]
    opt.warm_start(warm)
    first = opt.ask()
    assert first == warm[0]
    opt.tell(first, 5.0)
    # the already-told head is skipped if re-queued; the next pending serves
    opt.warm_start([warm[0]])
    assert opt.ask() == warm[1]


def fake_decls(n=6):
    return [
        TensorDeclaration(name=f"t{i}", num_elements=1 << 18, dtype="f32")
        for i in range(n)
    ]


@pytest.fixture()
def server():
    service = AutotuneService(
        world_size=1,
        autotune_level=1,
        max_samples=30,
        sampling_confidence_time_s=0.0,
        warmup_time_s=0.0,
    )
    srv = start_autotune_server(service, port=0)
    client = AutotuneClient(port=srv.server_address[1])
    yield service, client
    srv.shutdown()


def test_service_end_to_end_converges(server):
    service, client = server
    assert client.wait_until_ready(5.0)
    hp = client.register_tensors("mock_model", fake_decls())
    assert hp.buckets, "initial bucket assignment expected"

    for it in range(35):
        score = synthetic_score(hp.bucket_size, hp.is_hierarchical_reduce)
        client.report_metrics("mock_model", 0, it, score)
        hp, completed = client.ask_hyperparameters("mock_model", 0, it)
        if completed:
            break
    assert completed
    # locked to the best seen: near the 20 MiB peak (log2 = 24.32)
    assert abs(np.log2(hp.bucket_size) - 24.32) <= 2.5


def test_warmup_gating():
    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=10,
        sampling_confidence_time_s=0.0, warmup_time_s=3600.0,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        assert client.wait_until_ready(5.0)
        hp0 = client.register_tensors("m", fake_decls())
        client.report_metrics("m", 0, 1, 10.0)
        hp1, completed = client.ask_hyperparameters("m", 0, 1)
        # still in warmup: nothing sampled, hyperparameters unchanged
        assert not completed
        assert hp1.bucket_size == hp0.bucket_size
        assert service._managers["m"].sampling_counter == 0
    finally:
        srv.shutdown()


def test_execution_order_reorders_buckets(server):
    service, client = server
    client.register_tensors("om", fake_decls(3))
    spans = [
        {"action": "tensor_ready", "tensor_name": "t2", "start_time": 1},
        {"action": "tensor_ready", "tensor_name": "t0", "start_time": 2},
        {"action": "tensor_ready", "tensor_name": "t1", "start_time": 3},
    ]
    client.report_tensor_execution_order("om", spans)
    mgr = service._managers["om"]
    ordered = [td.name for td in mgr.ordered_tensor_list()]
    assert ordered == ["t2", "t0", "t1"]


@pytest.mark.slow
def test_autotune_session_rebuckets(group):
    """End-to-end: DDP + AutotuneSession against a live service re-buckets."""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=5,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        params = init_mlp(jax.random.PRNGKey(0), [16, 64, 64, 4])
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(), process_group=group,
            bucket_size_bytes=1 << 10,  # tiny start: several buckets
        )
        state = ddp.init(params)
        session = AutotuneSession(ddp, "ddp_model", client=client, interval=2)
        n0 = ddp.plan.num_buckets
        rng = np.random.RandomState(0)
        for i in range(8):
            batch = (
                jnp.asarray(rng.randn(16, 16), np.float32),
                jnp.asarray(rng.randn(16, 4), np.float32),
            )
            state, _ = ddp.train_step(state, batch)
            session.tick(16)
        # service proposes >=1MB buckets -> single bucket; plan must change
        assert ddp.plan.num_buckets != n0
        # training still works after re-bucketing
        state, losses = ddp.train_step(
            state,
            (jnp.asarray(rng.randn(16, 16), np.float32), jnp.asarray(rng.randn(16, 4), np.float32)),
        )
        assert np.isfinite(np.asarray(losses)).all()
    finally:
        srv.shutdown()


@pytest.mark.slow
def test_profile_bucket_order_measures_backward_depth(group):
    """Measured bucket costs reflect real backward depth: the first layer's
    gradients (deepest in backprop) cost more than the last layer's — the
    measurement the circular plan-order report could never make."""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    params = init_mlp(jax.random.PRNGKey(0), [64, 768, 768, 768, 768, 8])
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(), process_group=group,
        bucket_size_bytes=1,  # one leaf per bucket
    )
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(64, 64), np.float32),
        jnp.asarray(rng.randn(64, 8), np.float32),
    )
    t1 = profile_bucket_order(ddp, state, batch)
    t2 = profile_bucket_order(ddp, state, batch)
    times = [min(a, b) for a, b in zip(t1, t2)]  # noise floor

    def bucket_of(fragment):
        for i, spec in enumerate(ddp.plan.specs):
            if any(fragment in slot.name and "'w'" in slot.name for slot in spec.slots):
                return i
        raise AssertionError(fragment)

    assert times[bucket_of("layer0")] > times[bucket_of("layer4")], times


def test_profile_single_probe_machinery(group):
    """The one-compile probe's label join works on any backend: every bucket
    gets a ``bagua_probe/bucket=<i>`` scope that survives XLA fusion into the
    device trace, and arrivals come back attributed per bucket.  (Whether the
    timestamps reflect readiness is a scheduler property — only the TPU
    latency-hiding scheduler guarantees it, hence ``method="auto"`` picks the
    pruned probe on hosts; see ``profile_bucket_order``.)"""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    params = init_mlp(jax.random.PRNGKey(0), [16, 64, 64, 4])
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(), process_group=group,
        bucket_size_bytes=1 << 10,
    )
    state = ddp.init(params)
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(16, 16), np.float32),
        jnp.asarray(rng.randn(16, 4), np.float32),
    )
    times, capture = profile_bucket_order(
        ddp, state, batch, return_capture=True, method="single_probe"
    )
    assert len(times) == ddp.plan.num_buckets
    assert all(t >= 0.0 for t in times)
    assert capture["method"] == "single_probe"
    assert capture["labeled_buckets"] == ddp.plan.num_buckets
    assert "bagua_probe/bucket=0" in capture["hlo_text"]
    # auto on a host backend routes to the pruned probe
    t2, cap2 = profile_bucket_order(ddp, state, batch, return_capture=True)
    assert cap2["method"] == "pruned_per_bucket" and len(t2) == len(times)


@pytest.mark.slow
def test_session_profile_reports_measured_order(group):
    """profile_and_report ships measured spans; the service's learned partial
    order puts early-ready (late-layer) tensors first even though they were
    declared last."""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    service = AutotuneService(world_size=1, autotune_level=1)
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        params = init_mlp(jax.random.PRNGKey(0), [64, 768, 768, 768, 768, 8])
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(),
            process_group=group, bucket_size_bytes=1,
        )
        state = ddp.init(params)
        session = AutotuneSession(ddp, "prof_model", client=client)
        rng = np.random.RandomState(0)
        batch = (
            jnp.asarray(rng.randn(64, 64), np.float32),
            jnp.asarray(rng.randn(64, 8), np.float32),
        )
        session.profile_and_report(state, batch)
        assert session.profiled
        order = service._managers["prof_model"].tensor_partial_order
        assert order, "no measured order arrived at the service"
        w0 = next(k for k in order if "layer0" in k and "'w'" in k)
        w4 = next(k for k in order if "layer4" in k and "'w'" in k)
        assert order[w4] < order[w0]  # late layer ready earlier
    finally:
        srv.shutdown()


def test_plan_changes_are_step_agreed_under_drift():
    """Ranks must adopt each sampled plan at the same train_iter even when
    one rank's host loop runs rounds ahead (async dispatch drift) — the
    effective-from history guarantees identical answers per iter."""
    from bagua_tpu.defs import TensorDeclaration

    svc = AutotuneService(
        world_size=2, autotune_level=1, warmup_time_s=0,
        sampling_confidence_time_s=0, max_samples=4,
    )
    srv = start_autotune_server(svc, port=0)
    try:
        c = AutotuneClient(port=srv.server_address[1])
        decls = [
            TensorDeclaration(name=f"t{i}", num_elements=256, dtype="f32")
            for i in range(6)
        ]
        c.register_tensors("drift", decls)
        seen = {0: {}, 1: {}}

        def ask(rank, it):
            c.report_metrics("drift", rank, it, 100.0)
            hp, done = c.ask_hyperparameters("drift", rank, it)
            seen[rank][it] = (len(hp.buckets), hp.bucket_size, done)

        for it in range(1, 10):  # rank 0 races two rounds ahead
            ask(0, it)
            if it >= 3:
                ask(1, it - 2)
        for it in range(8, 10):
            ask(1, it)

        common = sorted(set(seen[0]) & set(seen[1]))
        assert len(common) >= 9
        for it in common:
            assert seen[0][it] == seen[1][it], (it, seen[0][it], seen[1][it])
        # sampling really happened and eventually locked
        assert svc._managers["drift"].sampling_counter == 4
        assert any(done for (_, _, done) in seen[0].values())
    finally:
        srv.shutdown()


def test_wire_dtype_knob_opt_in():
    """With tune_wire_dtype the optimizer explores wire_bf16 and the service
    reports it in proposals; without it the field stays at its False default."""
    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=25,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0, tune_wire_dtype=True,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        assert client.wait_until_ready(5.0)
        hp = client.register_tensors("wm", fake_decls())
        seen_bf16 = set()
        for it in range(30):
            # synthetic score: bf16 wire is strictly better
            score = synthetic_score(hp.bucket_size, hp.is_hierarchical_reduce)
            score += 25.0 if hp.wire_bf16 else 0.0
            client.report_metrics("wm", 0, it, score)
            hp, completed = client.ask_hyperparameters("wm", 0, it)
            seen_bf16.add(hp.wire_bf16)
            if completed:
                break
        assert completed
        assert seen_bf16 == {False, True}, "knob was never explored"
        assert hp.wire_bf16 is True, "locked hyperparameters missed the bf16 win"
    finally:
        srv.shutdown()


def test_wire_dtype_disabled_by_default(server):
    service, client = server
    hp = client.register_tensors("wd", fake_decls())
    for it in range(12):
        client.report_metrics("wd", 0, it, 1.0)
        hp, _ = client.ask_hyperparameters("wd", 0, it)
        assert hp.wire_bf16 is None  # dimension not tuned
    assert "wire_bf16" not in service._managers["wd"].optimizer.ask()


def test_untuned_service_preserves_user_wire_dtype(group):
    """Autotune without tune_wire_dtype must not clobber an explicitly
    configured wire_dtype on the algorithm."""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=3,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        params = init_mlp(jax.random.PRNGKey(0), [16, 32, 4])
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.05),
            GradientAllReduceAlgorithm(wire_dtype=jnp.bfloat16), process_group=group,
        )
        state = ddp.init(params)
        session = AutotuneSession(ddp, "keep_model", client=client, interval=1)
        rng = np.random.RandomState(0)
        for i in range(6):
            batch = (
                jnp.asarray(rng.randn(16, 16), np.float32),
                jnp.asarray(rng.randn(16, 4), np.float32),
            )
            state, _ = ddp.train_step(state, batch)
            session.tick(16)
            assert ddp.impl.wire_dtype == jnp.dtype(jnp.bfloat16), (
                "user wire_dtype clobbered by an untuned dimension"
            )
    finally:
        srv.shutdown()


def test_autotune_session_applies_wire_dtype(group):
    """A wire_bf16 proposal flips the gradient_allreduce impl's wire_dtype
    (re-jitting the step) and training continues finite."""
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=40,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0, tune_wire_dtype=True,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        params = init_mlp(jax.random.PRNGKey(0), [16, 32, 4])
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.05), GradientAllReduceAlgorithm(), process_group=group,
        )
        state = ddp.init(params)
        session = AutotuneSession(ddp, "wire_model", client=client, interval=1)
        rng = np.random.RandomState(0)
        saw_bf16 = False
        for i in range(25):
            batch = (
                jnp.asarray(rng.randn(16, 16), np.float32),
                jnp.asarray(rng.randn(16, 4), np.float32),
            )
            state, losses = ddp.train_step(state, batch)
            assert np.isfinite(np.asarray(losses)).all()
            session.tick(16)
            saw_bf16 = saw_bf16 or ddp.impl.wire_dtype is not None
            if saw_bf16:
                break
        assert saw_bf16, "the optimizer never proposed (or _apply never set) bf16 wire"
        # step still runs with the bf16 wire in force
        state, losses = ddp.train_step(
            state,
            (jnp.asarray(rng.randn(16, 16), np.float32), jnp.asarray(rng.randn(16, 4), np.float32)),
        )
        assert np.isfinite(np.asarray(losses)).all()
    finally:
        srv.shutdown()


def test_first_sample_labeled_with_preconfigured_wire_dtype():
    """A client that starts with bf16 on the wire must have its first score
    credited to wire_bf16=1, not the f32 default."""
    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=10,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0, tune_wire_dtype=True,
    )
    srv = start_autotune_server(service, port=0)
    try:
        client = AutotuneClient(port=srv.server_address[1])
        assert client.wait_until_ready(5.0)
        hp = client.register_tensors("pre", fake_decls(), current_wire_bf16=True)
        assert hp.wire_bf16 is True
        client.report_metrics("pre", 0, 0, 50.0)
        client.ask_hyperparameters("pre", 0, 0)
        opt = service._managers["pre"].optimizer
        wire_idx = [p.name for p in opt.params].index("wire_bf16")
        assert opt.xs[0][wire_idx] == 1.0
        assert opt.ys[0] == 50.0
    finally:
        srv.shutdown()
