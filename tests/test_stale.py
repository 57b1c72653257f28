"""Stale-sync (bounded-staleness gradient exchange) vs a pure-numpy oracle.

The relaxation contract, pinned from four sides:

* ``τ=0`` is OFF: bitwise-identical to :class:`GradientAllReduceAlgorithm`
  with overlap on — the lane's bitwise gate, repeated at tier-1 scale.
* The replay algebra (stale payload + error-feedback residual) matches a
  plain-numpy reimplementation on stacked per-rank buckets, the same
  oracle style as ``test_decentralized.py``.
* The staleness bound is enforced by construction: a rank held under a
  directive replays at most τ consecutive rounds, then is *forced* back
  to a fresh full contribution — counters never exceed τ.
* The two host-side knobs do exactly what they claim: the directive flip
  is recompile-free (data, not code), the τ switch is the single-recompile
  arc, and ``reset_staleness_state`` re-primes counters/residual.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bagua_tpu.algorithms import build_algorithm
from bagua_tpu.algorithms.stale import StaleSyncAlgorithm
from bagua_tpu.bucket import BucketPlan
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.mlp import init_mlp, mse_loss

N = 8
N_STEPS = 6
LR = 0.05
DIM_IN, DIM_OUT = 10, 3
TAU = 2
STALE_RANK = 2


def make_problem(seed=0):
    params = init_mlp(jax.random.PRNGKey(seed), [DIM_IN, 8, DIM_OUT])
    rng = np.random.RandomState(seed)
    xs = rng.randn(N_STEPS, N * 4, DIM_IN).astype(np.float32)
    ys = rng.randn(N_STEPS, N * 4, DIM_OUT).astype(np.float32)
    return params, xs, ys


def make_ddp(group, tau=0, overlap=False, lr=LR, momentum=None, **kw):
    opt = optax.sgd(lr, momentum=momentum) if momentum else optax.sgd(lr)
    return DistributedDataParallel(
        mse_loss,
        opt,
        StaleSyncAlgorithm(staleness_tau=tau),
        process_group=group,
        overlap=overlap,
        **kw,
    )


def counters(state):
    return np.asarray(state.algo_state["staleness"])


def flat_grad_fn(plan):
    def fn(flat, x, y):
        params = plan.debucketize([flat])
        g = jax.grad(mse_loss)(params, (x, y))
        return plan.bucketize(g)[0]

    return jax.jit(fn)


def test_stale_tau0_bitwise_matches_gradient_allreduce(group):
    """The relaxation must be genuinely OFF at τ=0 — same compiled family as
    the synchronous engine, overlap on, params bitwise after 6 steps."""
    params, xs, ys = make_problem(seed=11)

    def run(algo):
        ddp = DistributedDataParallel(
            mse_loss, optax.sgd(0.01, momentum=0.9), algo,
            process_group=group, bucket_size_bytes=1 << 12, overlap="auto",
        )
        state = ddp.init(params)
        for i in range(N_STEPS):
            state, _ = ddp.train_step(
                state, (jnp.asarray(xs[i]), jnp.asarray(ys[i]))
            )
        assert ddp.overlap_enabled
        return [np.asarray(l) for l in jax.tree.leaves(state.params)]

    got = run(StaleSyncAlgorithm(staleness_tau=0))
    ref = run(build_algorithm("gradient_allreduce"))
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_stale_replay_matches_oracle(group):
    """τ=2 with rank 2 under a directive from step 0: the engine must match
    the replay algebra reimplemented in numpy —

        contrib = stale            while directive AND counter < τ
                = g + residual     otherwise (and the residual telescopes)

    including the init-zero replay payload on the very first stale round."""
    params, xs, ys = make_problem(seed=1)
    ddp = make_ddp(group, tau=TAU, bucket_size_bytes=1 << 62)
    state = ddp.init(params)
    state = ddp.apply_degradation_directive(state, (STALE_RANK,))
    for i in range(N_STEPS):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))

    # ---- numpy oracle on the flat bucket ----
    plan = BucketPlan.from_tree(params, 1 << 62, align_elems=N)
    grad = flat_grad_fn(plan)
    w = np.asarray(plan.bucketize(params)[0]).astype(np.float64)
    dim = w.shape[0]
    stale = np.zeros((N, dim))
    resid = np.zeros((N, dim))
    cnt = np.zeros(N, np.int64)
    for step in range(N_STEPS):
        x = xs[step].reshape(N, -1, DIM_IN)
        y = ys[step].reshape(N, -1, DIM_OUT)
        g = np.stack([
            np.asarray(grad(jnp.asarray(w.astype(np.float32)), x[r], y[r]))
            for r in range(N)
        ]).astype(np.float64)
        contrib = np.empty_like(g)
        for r in range(N):
            use = r == STALE_RANK and cnt[r] < TAU
            contrib[r] = stale[r] if use else g[r] + resid[r]
            # replay payload = last raw fresh gradient, held across replays
            if not use:
                stale[r] = g[r]
            cnt[r] = cnt[r] + 1 if use else 0
        resid = resid + g - contrib
        w = w - LR * contrib.mean(axis=0)

    got = np.asarray(ddp.plan.bucketize(ddp.params_unstacked(state, 0))[0])
    np.testing.assert_allclose(got, w, rtol=2e-4, atol=1e-5)
    # the counter walked the oracle's cycle too
    assert counters(state)[STALE_RANK] == cnt[STALE_RANK]


def test_staleness_bound_forces_fresh_exchange(group):
    """A rank held under a directive forever still exchanges every τ+1
    rounds: counters cycle 1, 2, 0, 1, 2, 0 … and never exceed τ; ranks
    without a directive never move off 0."""
    params, xs, ys = make_problem(seed=2)
    ddp = make_ddp(group, tau=TAU, bucket_size_bytes=1 << 62)
    state = ddp.init(params)
    state = ddp.apply_degradation_directive(state, (STALE_RANK,))
    seen = []
    for step in range(7):
        i = step % N_STEPS
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))
        c = counters(state)
        seen.append(int(c[STALE_RANK]))
        assert c[STALE_RANK] <= TAU
        healthy = np.delete(c, STALE_RANK)
        assert (healthy == 0).all(), c
    # replay for τ rounds, then the forced fresh round resets the counter
    assert seen == [1, 2, 0, 1, 2, 0, 1]


def test_directive_flip_is_recompile_free(group):
    """The directive is a stacked int32 leaf — data, not code: flipping it
    must reuse the already-compiled step function verbatim."""
    params, xs, ys = make_problem(seed=3)
    ddp = make_ddp(group, tau=TAU)
    state = ddp.init(params)
    state, _ = ddp.train_step(state, (jnp.asarray(xs[0]), jnp.asarray(ys[0])))
    compiled_before = ddp.compiled_step()
    assert compiled_before is not None, "step did not compile"
    state = ddp.apply_degradation_directive(state, (STALE_RANK,))
    state, _ = ddp.train_step(state, (jnp.asarray(xs[1]), jnp.asarray(ys[1])))
    state = ddp.apply_degradation_directive(state, ())
    state, _ = ddp.train_step(state, (jnp.asarray(xs[2]), jnp.asarray(ys[2])))
    assert ddp.compiled_step() is compiled_before, "directive flip re-traced the step"


def test_directive_validates_ranks_and_knob(group):
    params, xs, ys = make_problem(seed=4)
    ddp = make_ddp(group, tau=TAU)
    state = ddp.init(params)
    with pytest.raises(ValueError, match="out of range"):
        ddp.apply_degradation_directive(state, (N,))
    plain = DistributedDataParallel(
        mse_loss, optax.sgd(LR), build_algorithm("gradient_allreduce"),
        process_group=group,
    )
    pstate = plain.init(params)
    with pytest.raises(AttributeError, match="no staleness knob"):
        plain.apply_degradation_directive(pstate, (0,))
    with pytest.raises(AttributeError, match="no staleness knob"):
        plain.apply_staleness(2, reason="planner")


def test_apply_staleness_is_the_single_recompile_switch(group):
    """τ switch arc: clears the compiled step (τ shapes the gate), re-proves
    the program, emits no-op False when τ is unchanged, rejects τ<0."""
    params, xs, ys = make_problem(seed=5)
    ddp = make_ddp(group, tau=0)
    state = ddp.init(params)
    state, _ = ddp.train_step(state, (jnp.asarray(xs[0]), jnp.asarray(ys[0])))
    assert ddp.compiled_step() is not None
    assert ddp.apply_staleness(TAU, reason="planner") is True
    assert ddp.impl.staleness_tau == TAU
    assert ddp.compiled_step() is None, "τ switch must invalidate the compiled step"
    assert ddp.apply_staleness(TAU, reason="planner") is False  # no-op
    with pytest.raises(ValueError):
        ddp.apply_staleness(-1, reason="planner")
    # the re-bounded program still trains
    state, _ = ddp.train_step(state, (jnp.asarray(xs[1]), jnp.asarray(ys[1])))


def test_reset_staleness_state_reprimes_replay(group):
    """After a τ re-raise the replay state is ancient: reset must pin every
    counter to τ (first directive round is forced fresh, rewriting the
    payload before any replay) and zero the error-feedback residual."""
    params, xs, ys = make_problem(seed=6)
    ddp = make_ddp(group, tau=TAU, bucket_size_bytes=1 << 62)
    state = ddp.init(params)
    state = ddp.apply_degradation_directive(state, (STALE_RANK,))
    for i in range(2):
        state, _ = ddp.train_step(state, (jnp.asarray(xs[i]), jnp.asarray(ys[i])))
    assert counters(state)[STALE_RANK] == 2
    resid = np.asarray(state.algo_state["residual"][0])
    assert np.abs(resid).max() > 0, "stale rounds must accrue residual"

    state = ddp.reset_staleness_state(state)
    assert (counters(state) == TAU).all()
    for leaf in state.algo_state["residual"]:
        assert np.abs(np.asarray(leaf)).max() == 0
    # counter at τ closes the gate: the very next round is fresh
    state, _ = ddp.train_step(state, (jnp.asarray(xs[2]), jnp.asarray(ys[2])))
    assert counters(state)[STALE_RANK] == 0


def test_stale_refuses_wire_quantization(group):
    """The replay algebra is defined on exact f32 buckets — stacking wire
    quantization's error feedback on top would compound two loops."""
    ddp = make_ddp(group, tau=TAU)
    with pytest.raises(ValueError, match="f32-only"):
        ddp.impl.set_bucket_precision(["int8"])
    with pytest.raises(ValueError):
        StaleSyncAlgorithm(staleness_tau=-1).reify(group)


def test_stale_convergence_tracks_bulk_sync(group):
    """Bounded staleness must stay a *relaxation*, not a different optimizer:
    on the fixed fixture, τ=2 with one degraded rank converges — loss
    strictly down an order of magnitude — and lands within a small factor
    of bulk sync's final loss."""
    params, _, _ = make_problem(seed=7)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(N * 4, DIM_IN).astype(np.float32))
    w_true = rng.randn(DIM_IN, DIM_OUT).astype(np.float32)
    y = jnp.asarray(np.asarray(x) @ w_true)

    def run(tau, directive):
        ddp = make_ddp(group, tau=tau, lr=0.02)
        state = ddp.init(params)
        if directive:
            state = ddp.apply_degradation_directive(state, directive)
        losses = []
        for _ in range(40):
            state, loss = ddp.train_step(state, (x, y))
            losses.append(float(np.mean(np.asarray(loss))))
        return losses

    bulk = run(0, ())
    stale = run(TAU, (STALE_RANK,))
    assert stale[-1] < 0.5 * stale[0], "stale-sync did not converge"
    assert abs(stale[-1] - bulk[-1]) < 0.05 * bulk[-1], (stale[-1], bulk[-1])
