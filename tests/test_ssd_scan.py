"""The chunked state-space scan against the recurrence it stands for, one
position after the other: values and every gradient at a length of several
chunks, with decays near 0 and near 1, in groups; which products are rounded;
and that no length or decay overflows.  Two implementations of it: the plain
``jax.numpy`` form every backend but the TPU runs, and the pair of Pallas
kernels the TPU runs, whose bodies run here under Pallas' interpreter.  Every
comparison runs both sides compiled (``helpers.compiled``)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels import ssd_scan as module
from bagua_tpu.kernels.ssd_scan import ssd_scan
from helpers import compiled
from oracles import rel_err


def interpreted(x, dt, a, b, c, chunk):
    """The TPU's kernels, their bodies run by the interpreter."""
    return module._scan_kernels(x, dt, a, b, c, chunk, True)


#: shapes the kernels take, small: chunks and state of 128, two heads of 64 a group
KERNEL_SHAPE = dict(heads=4, size=64, groups=2, state=128)
IMPLEMENTATIONS = {"plain": (ssd_scan, {}), "kernels": (interpreted, KERNEL_SHAPE)}


def recurrence(x, dt, a, b, c):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``, by
    ``lax.scan`` over the positions, every head with its group's ``B`` and
    ``C``."""
    batch, _, heads, size = x.shape
    groups, state = b.shape[-2:]
    b, c = (jnp.repeat(v, heads // groups, axis=2) for v in (b, c))

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * a)[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None]
        return s, jnp.einsum("bhds,bhs->bhd", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((batch, heads, size, state)),
                        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def drawn(seed, batch=2, t=64, heads=4, size=8, groups=2, state=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (batch, t, heads, size)).astype(dtype)
    b, c = (jax.random.normal(k, (batch, t, groups, state)).astype(dtype) for k in keys[1:3])
    dt = jax.nn.softplus(jax.random.normal(keys[3], (batch, t, heads)) - 1.0)
    return x, dt, b, c


#: a number a head: a step keeps all but a thousandth of the state, or a ten-millionth of it
DECAYS = {"near_one": (-1e-3, -2e-3, -5e-3, -1e-2), "near_zero": (-16.0, -12.0, -9.0, -7.0),
          "mixed": (-1e-3, -0.5, -4.0, -16.0)}


#: the plain form at three chunk lengths of 64 positions; the kernels at one chunk of 128 and
#: at three, where the carried state and its cotangent cross a chunk's edge, in two groups
CASES = {
    "chunk8": (ssd_scan, 8, {}), "chunk16": (ssd_scan, 16, {}), "chunk64": (ssd_scan, 64, {}),
    "kernels_one_chunk": (interpreted, 128, dict(KERNEL_SHAPE, t=128)),
    "kernels_three_chunks": (interpreted, 128, dict(KERNEL_SHAPE, t=384)),
}


@pytest.mark.parametrize("decays", sorted(DECAYS))
@pytest.mark.parametrize("case", list(CASES))
def test_the_chunked_scan_equals_the_recurrence_forward_and_in_every_gradient(decays, case):
    scan, chunk, shape = CASES[case]
    x, dt, b, c = drawn(0, **shape)
    a = jnp.asarray(DECAYS[decays])
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    # a decay is exp of a difference of two running sums of dt x a inside the chunk: where
    # they reach the hundreds (sixteen steps of the strongest decay) float32 leaves the
    # difference five digits, and ``a``'s gradient, a sum of such terms, 7e-5; in a chunk of
    # 128 they pass six hundred and leave a digit less (the plain form as the kernels)
    forward, backward = (2e-6, 2e-4) if chunk < 128 else (1e-5, 1e-3)
    args = (x, dt, a, b, c)

    def value_and_gradients(fn):
        """``fn``'s result and the five gradients of its probed sum, as one program."""
        def both(*args):
            out, pull = jax.vjp(fn, *args)
            return out, pull(probe.astype(out.dtype))
        return compiled(both, *args)

    with jax.default_matmul_precision("highest"):
        want, want_g = value_and_gradients(recurrence)
        got, got_g = value_and_gradients(lambda *args: scan(*args, chunk))
        assert got.shape == x.shape and got.dtype == x.dtype and rel_err(got, want) < forward
        if scan is interpreted:  # and the plain form it stands in for on the chip
            plain, plain_g = value_and_gradients(lambda *args: module._chunked(*args, chunk))
            assert rel_err(got, plain) < forward
            for name, g, w in zip(("x", "dt", "a", "b", "c"), got_g, plain_g):
                assert rel_err(g, w) < backward, (name, rel_err(g, w))
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got_g, want_g):
        assert np.linalg.norm(w) > 0, name
        assert rel_err(g, w) < backward, (name, rel_err(g, w))


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_a_head_reads_its_own_groups_b_and_c(implementation):
    scan, shape = IMPLEMENTATIONS[implementation]
    chunk = 128 if shape else 16
    x, dt, b, c = drawn(1, t=4 * chunk, **shape)
    a = jnp.asarray(DECAYS["mixed"])
    base = compiled(lambda *args: scan(*args, chunk), x, dt, a, b, c)
    other = compiled(lambda *args: scan(*args, chunk), x, dt, a, b.at[:, :, 1].add(1.0), c)
    # heads 0 and 1 are group 0, heads 2 and 3 group 1
    np.testing.assert_array_equal(other[:, :, :2], base[:, :, :2])
    assert rel_err(other[:, :, 2:], base[:, :, 2:]) > 0.1


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_the_scan_is_causal_and_carries_the_state_between_chunks(implementation):
    scan, shape = IMPLEMENTATIONS[implementation]
    chunk = 128 if shape else 16
    x, dt, b, c = drawn(2, t=4 * chunk, **shape)
    a = jnp.asarray(DECAYS["near_one"])
    def run(x):
        return compiled(lambda *args: scan(*args, chunk), x, dt, a, b, c)

    base = run(x)
    inside = 2 * chunk + chunk // 2  # in the third chunk
    later = run(x.at[:, inside:].set(3.0))
    np.testing.assert_array_equal(later[:, :inside], base[:, :inside])
    # positions of the third chunk read what the first chunk wrote into the state
    early = run(x.at[:, :chunk].set(0.0))
    assert rel_err(early[:, 2 * chunk:3 * chunk], base[:, 2 * chunk:3 * chunk]) > 0.05


def _equations(jaxpr, primitive):
    """Every equation of that primitive in a jaxpr, those inside a jitted
    function and inside a kernel's body too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner, primitive)


def test_the_kernels_round_the_products_alone_and_the_state_stays_float32():
    x, dt, b, c = drawn(3, dtype=jnp.bfloat16, t=1024, **KERNEL_SHAPE)
    a = jnp.asarray(DECAYS["mixed"])
    def under_ones(fn):
        def both(*args):
            out, pull = jax.vjp(fn, *args)
            return out, pull(jnp.ones_like(out))
        return both

    got, grads = compiled(under_ones(lambda *args: interpreted(*args, 128)), x, dt, a, b, c)
    assert got.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [v.dtype for v in (x, dt, a, b, c)]
    exact = [v.astype(jnp.float32) for v in (x, dt, a, b, c)]
    want, want_grads = compiled(under_ones(recurrence), *exact)
    assert rel_err(got.astype(jnp.float32), want) < 2e-2
    # the cotangents of dt and a are sums of differences that cancel on paper: rounded
    # operands must not keep them from cancelling (0.01 and 0.1 on the chip when the two ends
    # of a flow took differently rounded numbers, PERF.md section 6, PR 46)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), grads, want_grads):
        assert rel_err(g.astype(jnp.float32), w) < 2e-2, (name, rel_err(g.astype(jnp.float32), w))
    # both kernels: a product takes bf16 operands and accumulates in float32, or sums float32
    # columns at the highest precision; the state between chunks is float32 and never a product
    jaxpr = jax.make_jaxpr(
        lambda *args: jax.vjp(lambda *inner: interpreted(*inner, 128), *args)[1](
            jnp.ones(x.shape, x.dtype)))(x, dt, a, b, c)
    dots = list(_equations(jaxpr.jaxpr, "dot_general"))
    rounded = [eqn for eqn in dots if all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)]
    exact = [eqn for eqn in dots if all(v.aval.dtype == jnp.float32 for v in eqn.invars)]
    assert len(rounded) > 8 and exact and len(rounded) + len(exact) == len(dots)
    assert all(eqn.params["preferred_element_type"] == jnp.float32 for eqn in dots)
    assert all(eqn.params["precision"] is not None for eqn in exact)
    states = [v.aval for eqn in _equations(jaxpr.jaxpr, "pallas_call")
              for v in eqn.outvars if v.aval.ndim == 5]
    assert states and all(s.dtype == jnp.float32 and s.shape[2:] == (8, 128, 128) for s in states)


def test_bf16_operands_round_the_products_alone_and_the_state_stays_float32():
    x, dt, b, c = drawn(3, dtype=jnp.bfloat16)
    a = jnp.asarray(DECAYS["mixed"])
    got = compiled(lambda *args: ssd_scan(*args, chunk=16), x, dt, a, b, c)
    assert got.dtype == jnp.bfloat16
    want = compiled(recurrence, *(v.astype(jnp.float32) for v in (x, dt)), a,
                    *(v.astype(jnp.float32) for v in (b, c)))
    assert rel_err(got.astype(jnp.float32), want) < 2e-2
    # the three chunk products take bf16 operands and accumulate in float32; the product that
    # carries the state between chunks is float32 at the highest precision
    dots = [eqn for eqn in jax.make_jaxpr(lambda *args: ssd_scan(*args, chunk=16))(
        x, dt, a, b, c).eqns if eqn.primitive.name == "dot_general"]
    rounded = [eqn for eqn in dots if all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)]
    exact = [eqn for eqn in dots if all(v.aval.dtype == jnp.float32 for v in eqn.invars)]
    assert len(rounded) == 4 and len(rounded) + len(exact) == len(dots)  # scores, mixing, own, read
    assert all(eqn.params["preferred_element_type"] == jnp.float32 for eqn in rounded)
    assert exact and all(eqn.params["precision"] is not None for eqn in exact)


@pytest.mark.parametrize("implementation", list(IMPLEMENTATIONS))
def test_no_length_and_no_decay_overflows(implementation):
    """Every exponent is a later running sum less an earlier one: at 4,096
    positions of the strongest decay the running sum passes -60,000."""
    scan = IMPLEMENTATIONS[implementation][0]
    small = dict(size=4, state=8) if scan is ssd_scan else dict(size=64, state=128)
    x, dt, b, c = drawn(4, batch=1, t=4096, heads=2, groups=1, **small)
    a = jnp.asarray([-16.0, -1e-4])
    dt = dt + 1.0
    value, grads = compiled(jax.value_and_grad(
        lambda *args: jnp.sum(jnp.square(scan(*args, 128))), argnums=range(5)), x, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)
    assert rel_err(compiled(lambda *args: scan(*args, 128), x, dt, a, b, c),
                   compiled(recurrence, x, dt, a, b, c)) < 1e-4


def test_positions_that_do_not_divide_into_chunks_and_heads_into_groups_are_refused():
    x, dt, b, c = drawn(5, t=40)
    with pytest.raises(ValueError, match="no whole number"):
        ssd_scan(x, dt, jnp.asarray(DECAYS["mixed"]), b, c, chunk=16)
    # a sequence shorter than a chunk is one chunk
    short = compiled(lambda *args: ssd_scan(*args, chunk=128), x, dt, jnp.asarray(DECAYS["mixed"]), b, c)
    assert rel_err(short, compiled(recurrence, x, dt, jnp.asarray(DECAYS["mixed"]), b, c)) < 1e-5
    with pytest.raises(ValueError, match="no whole number"):
        ssd_scan(x[:, :, :3], dt[:, :, :3], jnp.asarray(DECAYS["mixed"][:3]), b, c, chunk=8)


@pytest.mark.parametrize("refused", ["positions", "state", "head_size"])
def test_on_a_tpu_a_shape_the_kernels_refuse_runs_the_plain_form_and_says_nothing(
        refused, monkeypatch, caplog, recwarn):
    """The choice is by backend and shape alone: with the backend steered to
    ``tpu`` a shape the kernels take reaches them, and one they refuse (a
    chunk, a state or a group's lanes that is no whole tile of 128) gives the
    plain form's bits with no warning and no log line."""
    reached = []

    real = module._scan_kernels

    def kernels(*args):  # as ``ssd_scan`` calls them: the five operands and the chunk
        reached.append(args[-1])
        return real(*args, True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(module, "_scan_kernels", kernels)
    a = jnp.asarray(DECAYS["mixed"])
    taken = drawn(6, t=256, **KERNEL_SHAPE)
    # eager: what is pinned is which form a call reaches, and a compiled ``ssd_scan`` that an
    # earlier case has traced is not traced again
    assert rel_err(ssd_scan(taken[0], taken[1], a, *taken[2:]),
                   module._chunked(taken[0], taken[1], a, *taken[2:], 128)) < 1e-5
    assert reached == [128]
    shape = {"positions": dict(KERNEL_SHAPE, t=64),  # one chunk of 64
             "state": dict(KERNEL_SHAPE, t=256, state=16),
             "head_size": dict(KERNEL_SHAPE, t=256, size=8)}[refused]
    x, dt, b, c = drawn(6, **shape)
    with caplog.at_level(logging.DEBUG):
        got = ssd_scan(x, dt, a, b, c)
    np.testing.assert_array_equal(got, module._chunked(x, dt, a, b, c, min(128, x.shape[1])))
    said = [r for r in caplog.records if r.levelno >= logging.WARNING or r.name.startswith("bagua")]
    assert reached == [128] and not said and not recwarn.list
