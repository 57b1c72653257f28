"""The chunked state-space scan against the recurrence it stands for, one
position after the other: values and every gradient at a length of several
chunks, with decays near 0 and near 1, in groups; which products are rounded;
and that no length or decay overflows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels.ssd_scan import ssd_scan


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


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


@pytest.mark.parametrize("decays", sorted(DECAYS))
@pytest.mark.parametrize("chunk", [8, 16, 64], ids=lambda c: f"chunk{c}")
def test_the_chunked_scan_equals_the_recurrence_forward_and_in_every_gradient(decays, chunk):
    x, dt, b, c = drawn(0)
    a = jnp.asarray(DECAYS[decays])
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        want = recurrence(x, dt, a, b, c)
        got = ssd_scan(x, dt, a, b, c, chunk=chunk)
        assert got.shape == x.shape and got.dtype == x.dtype and rel_err(got, want) < 2e-6

        def scalar(fn):
            return lambda *args: jnp.sum(probe * fn(*args))

        want_g = jax.grad(scalar(recurrence), argnums=range(5))(x, dt, a, b, c)
        got_g = jax.grad(scalar(lambda *args: ssd_scan(*args, chunk=chunk)), argnums=range(5))(
            x, dt, a, b, c)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got_g, want_g):
        assert np.linalg.norm(w) > 0, name
        # a decay is exp of a difference of two running sums of dt x a inside the chunk: where
        # they reach the hundreds (sixteen steps of the strongest decay) float32 leaves the
        # difference five digits, and ``a``'s gradient, a sum of such terms, 7e-5
        assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))


def test_a_head_reads_its_own_groups_b_and_c():
    x, dt, b, c = drawn(1)
    a = jnp.asarray(DECAYS["mixed"])
    base = ssd_scan(x, dt, a, b, c, chunk=16)
    other = ssd_scan(x, dt, a, b.at[:, :, 1].add(1.0), c, chunk=16)
    # heads 0 and 1 are group 0, heads 2 and 3 group 1
    np.testing.assert_array_equal(other[:, :, :2], base[:, :, :2])
    assert rel_err(other[:, :, 2:], base[:, :, 2:]) > 0.1


def test_the_scan_is_causal_and_carries_the_state_between_chunks():
    x, dt, b, c = drawn(2)
    a = jnp.asarray(DECAYS["near_one"])
    base = ssd_scan(x, dt, a, b, c, chunk=16)
    later = ssd_scan(x.at[:, 40:].set(3.0), dt, a, b, c, chunk=16)
    np.testing.assert_array_equal(later[:, :40], base[:, :40])
    # positions of the third chunk read what the first chunk wrote into the state
    early = ssd_scan(x.at[:, :16].set(0.0), dt, a, b, c, chunk=16)
    assert rel_err(early[:, 32:48], base[:, 32:48]) > 0.05


def test_bf16_operands_round_the_products_alone_and_the_state_stays_float32():
    x, dt, b, c = drawn(3, dtype=jnp.bfloat16)
    a = jnp.asarray(DECAYS["mixed"])
    got = ssd_scan(x, dt, a, b, c, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(v.astype(jnp.float32) for v in (x, dt)), a,
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


def test_no_length_and_no_decay_overflows():
    """Every exponent is a later running sum less an earlier one: at 4,096
    positions of the strongest decay the running sum passes -60,000."""
    x, dt, b, c = drawn(4, batch=1, t=4096, heads=2, size=4, groups=1, state=8)
    a = jnp.asarray([-16.0, -1e-4])
    dt = dt + 1.0
    value, grads = jax.value_and_grad(
        lambda *args: jnp.sum(jnp.square(ssd_scan(*args, chunk=128))), argnums=range(5))(
        x, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)
    assert rel_err(ssd_scan(x, dt, a, b, c, chunk=128), recurrence(x, dt, a, b, c)) < 1e-4


def test_positions_that_do_not_divide_into_chunks_and_heads_into_groups_are_refused():
    x, dt, b, c = drawn(5, t=40)
    with pytest.raises(ValueError, match="no whole number"):
        ssd_scan(x, dt, jnp.asarray(DECAYS["mixed"]), b, c, chunk=16)
    # a sequence shorter than a chunk is one chunk
    short = ssd_scan(x, dt, jnp.asarray(DECAYS["mixed"]), b, c, chunk=128)
    assert rel_err(short, recurrence(x, dt, jnp.asarray(DECAYS["mixed"]), b, c)) < 1e-5
    with pytest.raises(ValueError, match="no whole number"):
        ssd_scan(x[:, :, :3], dt[:, :, :3], jnp.asarray(DECAYS["mixed"][:3]), b, c, chunk=8)
