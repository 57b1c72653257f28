"""The chunked gated delta rule against the recurrence it stands for, one
position after the other: values and the gradients of all five operands at a
length of several chunks, at mild decays and at decays where ``exp(-G)`` over
one chunk overflows float32; which products are rounded; a length that is no
whole number of chunks refused by name; what the rule is not (one decay a head,
``beta`` left in (0, 1)).  One implementation, the ``jax.numpy`` composition
every backend runs.  Every comparison runs both sides compiled
(``helpers.compiled``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels import delta_rule as module
from bagua_tpu.kernels.delta_rule import gated_delta_rule
from helpers import compiled
from oracles import rel_err


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


@pytest.mark.parametrize("chunk", [32, 16], ids=["chunks_of_32", "chunks_of_16"])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_values_and_all_five_gradients_equal_the_recurrences(decay, chunk):
    operands = drawn(3, decay)
    probe = jax.random.normal(jax.random.PRNGKey(4), operands[2].shape)
    assert operands[0].shape[1] == 96 > 2 * chunk  # the state crosses chunks
    if decay == "strong":  # exp(-G) over 32 positions is infinite in float32
        assert float(jnp.max(-jnp.sum(operands[3][:, :32], axis=1))) > 89.0
    with jax.default_matmul_precision("highest"):
        got = compiled(both_passes(lambda *a: gated_delta_rule(*a, chunk=chunk), probe), *operands)
        want = compiled(both_passes(recurrence, probe), *operands)
    for name, g, w in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert np.all(np.isfinite(np.asarray(g))), name
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


def test_operands_are_rounded_once_and_the_state_is_not():
    """In bf16: the result comes back in ``v``'s type and lies as near the
    float32 recurrence on the same rounded operands as bf16 products allow; the
    running sums, the decays and the carried state stay float32."""
    operands = drawn(7, "mild", dtype=jnp.bfloat16)
    wide = tuple(x.astype(jnp.float32) for x in operands)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: gated_delta_rule(*a, chunk=32), *operands)
        want = compiled(recurrence, *wide)
    assert got.dtype == jnp.bfloat16 and rel_err(got, want) < 2e-2
    jaxpr = str(jax.make_jaxpr(lambda *a: gated_delta_rule(*a, chunk=32))(*operands))
    assert "cumsum" in jaxpr and "triangular_solve" in jaxpr
    for line in jaxpr.splitlines():
        if " cumsum[" in line or " triangular_solve[" in line or " exp " in line:
            assert ":f32[" in line and "bf16" not in line.split("=")[0], line


def test_a_sequence_shorter_than_a_chunk_is_one_chunk_and_any_other_remainder_is_refused():
    operands = drawn(9, "mild", t=24)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: gated_delta_rule(*a, chunk=64), *operands)
        want = compiled(recurrence, *operands)
    assert rel_err(got, want) < 2e-5
    with pytest.raises(ValueError, match="gated_delta_rule: 24 positions are no whole number of chunks of 16"):
        gated_delta_rule(*operands, chunk=16)
    assert module.SUB_BLOCKS == 4  # 16 positions a sub-block at the configuration's chunk of 64


@pytest.mark.parametrize("fault", ["one_decay_a_head", "beta_left_in_0_1"])
def test_the_rule_is_neither_of_its_simpler_relatives(fault):
    """What the benchmark's broken programs compute is another function: the
    channels' mean decay in every channel, or ``beta`` without its doubling."""
    q, k, v, g, beta = drawn(11, "mild")
    if fault == "one_decay_a_head":
        other = (q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta)
    else:
        other = (q, k, v, g, beta / 2.0)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: gated_delta_rule(*a, chunk=32), q, k, v, g, beta)
        changed = compiled(lambda *a: gated_delta_rule(*a, chunk=32), *other)
        assert rel_err(changed, compiled(recurrence, *other)) < 2e-5
    assert rel_err(changed, got) > 0.05
