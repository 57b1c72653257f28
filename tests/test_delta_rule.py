"""The chunked gated delta rule against the recurrence it stands for, one
position after the other: values and the gradients of all five operands at a
length of several chunks, at mild decays and at decays where ``exp(-G)`` over
one chunk overflows float32; which products are rounded; a length that is no
whole number of chunks refused by name; what the rule is not (one decay a head,
``beta`` left in (0, 1)).  Two implementations of it: the plain ``jax.numpy``
composition every backend but the TPU runs, and the pair of Pallas kernels the
TPU runs, whose bodies run here under Pallas' interpreter at a shape the
kernels take (chunks of 64, keys and values of 128).  Every comparison runs
both sides compiled (``helpers.compiled``)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels import delta_rule as module
from bagua_tpu.kernels.delta_rule import gated_delta_rule
from helpers import compiled
from oracles import rel_err


def interpreted(q, k, v, g, beta, chunk=64):
    """The TPU's kernels, their bodies run by the interpreter."""
    return module._rule_kernels(q, k, v, g, beta, chunk, True)


#: a shape the kernels take, small: one sequence, two heads of 128 keys and values
KERNEL_SHAPE = dict(batch=1, heads=2, size=128)
#: the rule, its chunk, and the operands' shape: three chunks each
IMPLEMENTATIONS = {"plain": (gated_delta_rule, 32, dict(t=96)),
                   "kernels": (interpreted, 64, dict(KERNEL_SHAPE, t=192))}


def recurrence(q, k, v, g, beta):
    """``S' = exp(g_t)[:, None] S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T``, ``o_t = S_t^T q_t``, by ``lax.scan`` over the positions."""
    batch, _, heads, size = k.shape

    def step(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = jnp.exp(g_t)[..., None] * s
        held = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + beta_t[..., None, None] * k_t[..., None] * (v_t - held)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((batch, heads, size, v.shape[-1])),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


#: the log of a step's decay is minus ``exp`` of a uniform draw between these: ``mild`` keeps
#: 98% to 37% of a channel a step; ``strong`` reaches exp(-12) a step, 384 over a chunk of 32
DECAYS = {"mild": (-4.0, 0.0), "strong": (-4.0, 2.5)}


def drawn(seed, decay="mild", batch=2, t=96, heads=2, size=16, dtype=jnp.float32):
    """Operands as the layer makes them: ``q`` and ``k`` L2-normed (``q`` with
    the score's scale), ``beta`` in (0, 2), a decay a channel."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, t, heads, size)) for key in keys[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / size ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(keys[3], (batch, t, heads, size), minval=DECAYS[decay][0],
                                    maxval=DECAYS[decay][1]))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (batch, t, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def both_passes(fn, probe):
    def run(*operands):
        out, pull = jax.vjp(fn, *operands)
        return (out,) + pull(probe.astype(out.dtype))
    return run


#: the plain form at two chunk lengths of 96 positions; the kernels at one chunk of 64 and at
#: three, where the carried state and its cotangent cross a chunk's edge
CASES = {
    "chunks_of_32": (gated_delta_rule, 32, {}), "chunks_of_16": (gated_delta_rule, 16, {}),
    "kernels_one_chunk": (interpreted, 64, dict(KERNEL_SHAPE, t=64)),
    "kernels_three_chunks": IMPLEMENTATIONS["kernels"],
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_values_and_all_five_gradients_equal_the_recurrences(decay, case):
    rule, chunk, shape = CASES[case]
    operands = drawn(3, decay, **shape)
    probe = jax.random.normal(jax.random.PRNGKey(4), operands[2].shape)
    if case != "kernels_one_chunk":  # the state crosses chunks
        assert operands[0].shape[1] > 2 * chunk
    if decay == "strong":  # exp(-G) over 32 positions is infinite in float32
        assert float(jnp.max(-jnp.sum(operands[3][:, :32], axis=1))) > 89.0
    with jax.default_matmul_precision("highest"):
        got = compiled(both_passes(lambda *a: rule(*a, chunk=chunk), probe), *operands)
        want = compiled(both_passes(recurrence, probe), *operands)
    for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert g.shape == w.shape and np.all(np.isfinite(np.asarray(g))), name
        assert np.linalg.norm(w) > 0 and rel_err(g, w) < 2e-5, (name, rel_err(g, w))


def test_no_exponential_of_a_positive_running_sum_is_formed(monkeypatch):
    """Every ``exp`` the composition forms, at the strong decays, has an
    argument that is at most zero; none is formed inside the scan over the
    chunks (its argument would be a tracer of the scan's body, and would not
    leave it).  The backward pass forms no other: the derivative of ``exp`` is
    its value."""
    operands = drawn(5, "strong", batch=1, t=64)
    seen = []

    class Recording:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def exp(self, x):
            seen.append(jnp.max(x))
            return jnp.exp(x)

    monkeypatch.setattr(module, "jnp", Recording())
    out, largest = compiled(lambda *a: (gated_delta_rule(*a, chunk=32), jnp.stack(seen)), *operands)
    assert np.all(np.isfinite(np.asarray(out)))
    assert len(largest) >= 6 and float(largest.max()) <= 0.0, largest
    # the split that one decay a head allows does overflow here
    assert not np.isfinite(float(jnp.max(jnp.exp(-jnp.cumsum(operands[3][:, :32], axis=1)))))


def test_the_kernels_form_no_exponential_of_a_positive_running_sum(monkeypatch):
    """The same of both kernels' bodies, whose values do not leave them: an
    ``exp`` that answers a positive argument with NaN leaves the result and
    all five gradients finite at the strong decays, over two chunks; it is
    reached (both bodies form dozens) and it does poison what it is given."""
    operands = drawn(5, "strong", batch=1, t=128, heads=1, size=128)
    probe = jnp.ones(operands[2].shape)
    calls = []

    class Poisoned:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def exp(self, x):
            calls.append(x.shape)
            return jnp.where(x > 0, jnp.nan, jnp.exp(x))

    assert np.isnan(float(Poisoned().exp(jnp.float32(1e-3))))
    calls.clear()
    monkeypatch.setattr(module, "jnp", Poisoned())
    got = compiled(both_passes(interpreted, probe), *operands)
    assert len(calls) > 40, len(calls)
    for name, g in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got):
        assert np.all(np.isfinite(np.asarray(g))), name
    # the split that one decay a head allows does overflow over a chunk of these
    assert not np.isfinite(float(jnp.max(jnp.exp(-jnp.cumsum(operands[3][:, :64], axis=1)))))


def _equations(jaxpr, primitive):
    """Every equation of that primitive in a jaxpr, those inside a jitted
    function and inside a kernel's body too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner, primitive)


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_operands_are_rounded_once_and_the_state_is_not(implementation):
    """In bf16: the result comes back in ``v``'s type and lies as near the
    float32 recurrence on the same rounded operands as bf16 products allow; the
    running sums, the decays, the solve and the carried state stay float32."""
    rule, chunk, shape = IMPLEMENTATIONS[implementation]
    operands = drawn(7, "mild", dtype=jnp.bfloat16, **shape)
    wide = tuple(x.astype(jnp.float32) for x in operands)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: rule(*a, chunk=chunk), *operands)
        want = compiled(recurrence, *wide)
    assert got.dtype == jnp.bfloat16 and rel_err(got, want) < 2e-2
    if implementation == "plain":
        jaxpr = str(jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=32))(*operands))
        assert "cumsum" in jaxpr and "triangular_solve" in jaxpr
        for line in jaxpr.splitlines():
            if " cumsum[" in line or " triangular_solve[" in line or " exp " in line:
                assert ":f32[" in line and "bf16" not in line.split("=")[0], line
        return
    # both kernels: a product takes operands in v's type and accumulates in float32, or it is
    # the solve's, float32 at the highest precision; every exponential is float32; the states
    # kept between the kernels are float32, (value size, key size) a chunk and head
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(interpreted, *a)[1](jnp.ones_like(a[2])))(*operands).jaxpr
    dots = list(_equations(jaxpr, "dot_general"))
    rounded = [eqn for eqn in dots if all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)]
    exact = [eqn for eqn in dots if all(v.aval.dtype == jnp.float32 for v in eqn.invars)]
    assert len(rounded) > 16 and len(exact) > 16 and len(rounded) + len(exact) == len(dots)
    assert all(eqn.params["preferred_element_type"] == jnp.float32 for eqn in dots)
    assert all(eqn.params["precision"] is not None for eqn in exact)
    exps = list(_equations(jaxpr, "exp"))
    assert len(exps) > 40 and all(eqn.outvars[0].aval.dtype == jnp.float32 for eqn in exps)
    kept = [v.aval for eqn in _equations(jaxpr, "pallas_call") for v in eqn.outvars if v.aval.ndim == 5]
    assert all(s.dtype == jnp.float32 and s.shape[:3] == (1, 3, 2) for s in kept)
    assert (128, 128) in [s.shape[3:] for s in kept]  # the states


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_rounded_operands_leave_the_sums_that_cancel_on_paper_cancelling(implementation):
    """In bf16 the gradients of ``g`` (a difference of running sums of the
    flows into and out of every position) and of ``beta`` lie as near a
    float32 oracle on the same rounded operands as the other three: two ends
    of one flow that took differently rounded numbers read ten times off
    (``PERF.md`` section 6, PR 46)."""
    rule, chunk, shape = IMPLEMENTATIONS[implementation]
    operands = drawn(13, "mild", dtype=jnp.bfloat16, **shape)
    probe = jax.random.normal(jax.random.PRNGKey(14), operands[2].shape)
    wide = tuple(x.astype(jnp.float32) for x in operands)
    got = compiled(both_passes(lambda *a: rule(*a, chunk=chunk), probe), *operands)
    with jax.default_matmul_precision("highest"):
        want = compiled(both_passes(recurrence, probe), *wide)
    assert [g.dtype for g in got[1:]] == [x.dtype for x in operands]
    # every one reads 0.0025 to 0.0039 in either implementation: twice that is the room
    for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert rel_err(g.astype(jnp.float32), w) < 8e-3, (name, rel_err(g.astype(jnp.float32), w))


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_keys_that_repeat_at_beta_near_2_are_solved_as_exactly_as_any(implementation):
    """Keys that all but repeat inside a chunk (cosine 0.99) under ``beta`` of
    1.9 and little decay: the system ``I + Diag(beta) A`` then has entries near
    2 everywhere under its diagonal and its inverse alternates.  A solve by
    substitution, by rows or by blocks, keeps float32's digits; a finite series
    in the system's powers, exact on paper, lost all but one here (its terms
    pass a hundred thousand where the sum is of size one)."""
    rule, chunk, shape = IMPLEMENTATIONS[implementation]
    q, k, v, g, beta = drawn(15, "mild", **shape)
    base = jax.random.normal(jax.random.PRNGKey(16), (1, 1) + k.shape[2:])
    k = base + 0.1 * k * k.shape[-1] ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    operands = (q, k, v, g * 0.01, jnp.full_like(beta, 1.9))
    probe = jax.random.normal(jax.random.PRNGKey(17), v.shape)
    with jax.default_matmul_precision("highest"):
        got = compiled(both_passes(lambda *a: rule(*a, chunk=chunk), probe), *operands)
        want = compiled(both_passes(recurrence, probe), *operands)
    assert float(jnp.min(jnp.einsum("bthc,bthc->bth", k[:, 1:], k[:, :-1]))) > 0.97
    for name, g_, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert rel_err(g_, w) < 1e-4, (name, rel_err(g_, w))


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_a_sequence_shorter_than_a_chunk_is_one_chunk_and_any_other_remainder_is_refused(
        implementation, monkeypatch):
    if implementation == "kernels":  # on a TPU: the plain form takes the short sequence, in silence
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(module, "_rule_kernels", None)
    operands = drawn(9, "mild", t=24, **(KERNEL_SHAPE if implementation == "kernels" else {}))
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: gated_delta_rule(*a, chunk=64), *operands)
        want = compiled(recurrence, *operands)
    assert rel_err(got, want) < 2e-5
    with pytest.raises(ValueError, match="gated_delta_rule: 24 positions are no whole number of chunks of 16"):
        gated_delta_rule(*operands, chunk=16)
    assert module.SUB_BLOCKS == 4  # 16 positions a sub-block at the configuration's chunk of 64


@pytest.mark.parametrize("refused", ["a_chunk_of_32", "a_size_of_64", "a_value_size_of_64"])
def test_on_a_tpu_a_shape_the_kernels_refuse_runs_the_plain_form_and_says_nothing(
        refused, monkeypatch, caplog, recwarn):
    """The choice is by backend and shape alone: with the backend steered to
    ``tpu`` a shape the kernels take reaches them, and one they refuse (a
    chunk that is not 64, keys or values that are no whole tile of 128) gives
    the plain form's bits with no warning and no log line."""
    reached = []
    real = module._rule_kernels

    def kernels(*args):  # as ``gated_delta_rule`` calls them: the five operands and the chunk
        reached.append(args[-1])
        return real(*args, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(module, "_rule_kernels", kernels)
    taken = drawn(6, t=128, **KERNEL_SHAPE)
    # eager: what is pinned is which form a call reaches
    assert rel_err(gated_delta_rule(*taken), module._chunked(*taken, 64)) < 1e-5
    assert reached == [64]
    chunk = 32 if refused == "a_chunk_of_32" else 64
    q, k, v, g, beta = drawn(6, t=128, **dict(KERNEL_SHAPE, size=128 if refused == "a_chunk_of_32" else 64))
    if refused == "a_size_of_64":  # keys of 64 under values of 128
        v = jnp.concatenate([v, v], axis=-1)
    if refused == "a_value_size_of_64":
        q, k, g = (jnp.concatenate([x, x], axis=-1) for x in (q, k, g))
    with caplog.at_level(logging.DEBUG):
        got = gated_delta_rule(q, k, v, g, beta, chunk)
    np.testing.assert_array_equal(got, module._chunked(q, k, v, g, beta, chunk))
    said = [r for r in caplog.records if r.levelno >= logging.WARNING or r.name.startswith("bagua")]
    assert reached == [64] and not said and not recwarn.list


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
@pytest.mark.parametrize("fault", ["one_decay_a_head", "beta_left_in_0_1"])
def test_the_rule_is_neither_of_its_simpler_relatives(fault, implementation):
    """What the benchmark's broken programs compute is another function: the
    channels' mean decay in every channel, or ``beta`` without its doubling."""
    rule, chunk, shape = IMPLEMENTATIONS[implementation]
    q, k, v, g, beta = drawn(11, "mild", **shape)
    if fault == "one_decay_a_head":
        other = (q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta)
    else:
        other = (q, k, v, g, beta / 2.0)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: rule(*a, chunk=chunk), q, k, v, g, beta)
        changed = compiled(lambda *a: rule(*a, chunk=chunk), *other)
        assert rel_err(changed, compiled(recurrence, *other)) < 2e-5
    assert rel_err(changed, got) > 0.05
