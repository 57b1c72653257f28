"""The dropless expert layer by its four callers' ``(k, held)``: the rows of
its buffer, that no term is lost at the worst load, the expert with no gate,
the tiles the two new shapes take, and the two passes over a bounded buffer.
Both sides of every comparison run compiled (``helpers.compiled``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.nemotron_h import relu2
from bagua_tpu.parallel.moe import dropless
from bagua_tpu.parallel.moe.dropless import (
    GMM_TILES, collect, dropless_experts, gmm_tiling, sigmoid_topk_route, spread)
from helpers import compiled

#: ``(choices a token, experts of the model, experts held)`` of the four callers:
#: ``glm_moe``, ``lfm2_moe``, ``smallthinker_moe``, ``nemotron_h``
CALLERS = {"glm": (4, 64, 8), "lfm2": (4, 32, 8), "smallthinker": (6, 64, 8),
           "nemotron_h": (22, 512, 8)}
TOKENS, HIDDEN, WIDTH = 48, 16, 24


def _kernels(key, held, gated):
    keys = jax.random.split(key, 3)
    gate = 0.3 * jax.random.normal(keys[0], (held, HIDDEN, WIDTH)) if gated else None
    return (gate, 0.3 * jax.random.normal(keys[1], (held, HIDDEN, WIDTH)),
            0.3 * jax.random.normal(keys[2], (held, WIDTH, HIDDEN)))


def dense_experts(x, chosen, weights, gate, up, down, first, activation):
    """Every held expert on every token under its weight, zero where it was
    not chosen: no sort, no buffer."""
    out = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1, keepdims=True)
        raised = x @ up[e]
        hidden = activation(raised) if gate is None else activation(x @ gate[e]) * raised
        out = out + w * (hidden @ down[e])
    return out


def _buffer_rows(fn, *args):
    """The rows of every grouped product's left operand in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                found.append(eqn.invars[0].aval.shape[0])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("caller", CALLERS)
def test_the_buffer_has_tokens_times_the_fewer_of_choices_and_held_rows(caller):
    k, experts, held = CALLERS[caller]
    gated = caller != "nemotron_h"
    gate, up, down = _kernels(jax.random.PRNGKey(0), held, gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, HIDDEN))
    router = jax.random.normal(jax.random.PRNGKey(2), (HIDDEN, experts))

    def layer(x, up, down):
        chosen, weights = sigmoid_topk_route(x, router, jnp.zeros((experts,)), k, 1.0)
        return jnp.sum(dropless_experts(x, chosen, weights, gate, up, down, held=(0, held),
                                        num_experts=experts,
                                        activation=jax.nn.silu if gated else relu2))

    rows = _buffer_rows(jax.grad(layer, argnums=(0, 1, 2)), x, up, down)
    assert rows and set(rows) == {TOKENS * min(k, held)}
    # the three callers whose tokens make no more choices than experts are held keep tokens x k
    assert (TOKENS * min(k, held) == TOKENS * k) == (caller != "nemotron_h")


@pytest.mark.parametrize("caller", CALLERS)
def test_no_term_is_lost_when_every_token_chooses_every_held_expert(caller):
    """The worst load: all ``min(k, held)`` of a token's choices that can be
    held are, so every row of the buffer is live; value and gradients equal
    the dense sum over the held experts."""
    k, experts, held = CALLERS[caller]
    first = 8
    gated = caller != "nemotron_h"
    activation = jax.nn.silu if gated else relu2
    gate, up, down = _kernels(jax.random.PRNGKey(3), held, gated)
    x = jax.random.normal(jax.random.PRNGKey(4), (TOKENS, HIDDEN))
    probe = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, HIDDEN))
    # each token: as many held experts as it has choices for, in an order of its own, the rest
    # of its choices elsewhere
    rng = np.random.default_rng(0)
    chosen = np.empty((TOKENS, k), np.int32)
    for t in range(TOKENS):
        here = first + rng.permutation(held)[:min(k, held)]
        elsewhere = (first + held + rng.permutation(experts - held)[:k - len(here)]) % experts
        chosen[t] = rng.permutation(np.concatenate([here, elsewhere]))
    chosen = jnp.asarray(chosen)
    weights = jax.random.uniform(jax.random.PRNGKey(6), (TOKENS, k), minval=0.2, maxval=1.0)
    assert int(jnp.sum((chosen >= first) & (chosen < first + held))) == TOKENS * min(k, held)

    def of(layer):
        def scalar(x, weights, up, down):
            return jnp.sum(probe * layer(x, chosen, weights, gate, up, down))
        return compiled(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3)), x, weights, up, down)

    got = of(lambda *a: dropless_experts(*a, held=(first, held), num_experts=experts,
                                         activation=activation))
    want = of(lambda *a: dense_experts(*a, first, activation))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, w, name in zip(got[1], want[1], ("x", "weights", "up", "down")):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("first", [0, 504], ids=["share0", "share63"])
def test_an_expert_with_no_gate_is_two_products_around_the_callers_activation(first):
    k, experts, held = CALLERS["nemotron_h"]
    _, up, down = _kernels(jax.random.PRNGKey(7), held, gated=False)
    x = jax.random.normal(jax.random.PRNGKey(8), (TOKENS, HIDDEN))
    router = jax.random.normal(jax.random.PRNGKey(9), (HIDDEN, experts))
    bias = 0.002 * jax.random.normal(jax.random.PRNGKey(10), (experts,))

    def of(experts_fn):
        def scalar(x, router, up, down):
            chosen, weights = sigmoid_topk_route(x, router, bias, k, 5.0)
            return jnp.sum(jnp.sin(experts_fn(x, chosen, weights, None, up, down)))
        return compiled(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3)), x, router, up, down)

    got = of(lambda *a: dropless_experts(*a, held=(first, held), num_experts=experts,
                                         activation=relu2))
    want = of(lambda *a: dense_experts(*a, first, relu2))
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, w, name in zip(got[1], want[1], ("x", "router", "up", "down")):
        np.testing.assert_allclose(g, w, rtol=5e-4, atol=2e-5, err_msg=name)
    # one reader of the buffer and two products: a third of the gated unit's grouped products
    chosen, weights = compiled(lambda x, router: sigmoid_topk_route(x, router, bias, k, 5.0),
                               x, router)
    products = _buffer_rows(lambda x: dropless_experts(
        x, chosen, weights, None, up, down, held=(first, held), num_experts=experts,
        activation=relu2), x)
    assert len(products) == 2


@pytest.mark.parametrize("shape", [(1024, 2688), (2688, 1024)], ids=["up", "down"])
def test_the_latent_experts_two_shapes_take_measured_tiles_that_divide_them(shape):
    contraction, columns = shape
    assert shape in GMM_TILES
    for rows in (2816, 65536):
        tile = gmm_tiling(rows, contraction, columns)
        assert tile == (GMM_TILES[shape][0], GMM_TILES[shape][1] or contraction, GMM_TILES[shape][2])
        assert contraction % tile[1] == 0 and columns % tile[2] == 0 and tile[2] % 128 == 0
        assert tile[0] % 8 == 0
    # the accepted callers' shapes keep their tiles
    assert gmm_tiling(32768, 2048, 1536) == (512, 1024, 768)
    assert gmm_tiling(32768, 2048, 1792) == (128, 2048, 896)
    assert gmm_tiling(49152, 2560, 768) == (256, 1280, 768)
    assert gmm_tiling(49152, 768, 2560) == (256, 768, 1280)


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
def test_the_two_passes_over_a_bounded_buffer_are_each_others_transpose(weighted):
    """``spread`` and ``collect`` where the buffer is shorter than the
    assignments: against the same passes written as dense selections, values
    and both gradients, with live rows up to the bound and with few."""
    tokens, fan, slots, width = 12, 5, 2, 6
    total, rows = tokens * fan, tokens * slots
    rng = np.random.default_rng(1)
    for n_live in (rows, 7, 0):
        # the live assignments: at most ``slots`` a token, their rows first in the order
        picked = [(t, j) for t in range(tokens) for j in rng.permutation(fan)[:slots]]
        live = [t * fan + j for t, j in (picked[i] for i in rng.permutation(len(picked))[:n_live])]
        rest = [a for a in rng.permutation(total) if a not in set(live)]
        perm = jnp.asarray(live + rest, jnp.int32)
        order = (perm[:rows], jnp.argsort(perm).astype(jnp.int32), jnp.int32(n_live))
        src = jnp.asarray(rng.normal(size=(tokens, width)), jnp.float32)
        buffer = jnp.asarray(rng.normal(size=(rows, width)), jnp.float32)
        weight = jnp.asarray(rng.uniform(0.5, 1.5, size=(tokens, fan)), jnp.float32)
        scale = jnp.asarray(rng.uniform(0.5, 1.5, size=(rows,)), jnp.float32)
        # row r of the buffer holds assignment perm[r], live where r < n_live
        holds = np.zeros((rows, total), np.float32)
        holds[np.arange(n_live), np.asarray(perm[:n_live])] = 1.0
        holds = jnp.asarray(holds)

        def dense_collect(buffer, weight):
            per_choice = (holds.T @ buffer).reshape(tokens, fan, width)
            return jnp.sum((weight if weighted else 1.0)[..., None] * per_choice, axis=1) \
                if weighted else jnp.sum(per_choice, axis=1)

        def dense_spread(src, scale):
            out = holds @ jnp.repeat(src, fan, axis=0)
            return scale[:, None] * out if weighted else out

        w, s = (weight, scale) if weighted else (None, None)
        np.testing.assert_allclose(
            compiled(lambda b, w, order: collect(b, w, order, fan), buffer, w, order),
            compiled(dense_collect, buffer, weight), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            compiled(lambda a, c, order: spread(a, c, order, fan), src, s, order),
            compiled(dense_spread, src, scale), rtol=1e-5, atol=1e-6)
        probe_t = jnp.asarray(rng.normal(size=(tokens, width)), jnp.float32)
        probe_r = jnp.asarray(rng.normal(size=(rows, width)), jnp.float32)
        if weighted:
            got = compiled(jax.grad(
                lambda b, w: jnp.sum(probe_t * collect(b, w, order, fan)), (0, 1)), buffer, weight)
            want = compiled(jax.grad(
                lambda b, w: jnp.sum(probe_t * dense_collect(b, w)), (0, 1)), buffer, weight)
            got += compiled(jax.grad(
                lambda a, c: jnp.sum(probe_r * spread(a, c, order, fan)), (0, 1)), src, scale)
            want += compiled(jax.grad(
                lambda a, c: jnp.sum(probe_r * dense_spread(a, c)), (0, 1)), src, scale)
        else:
            got = (compiled(jax.grad(
                       lambda b: jnp.sum(probe_t * collect(b, None, order, fan))), buffer),
                   compiled(jax.grad(
                       lambda a: jnp.sum(probe_r * spread(a, None, order, fan))), src))
            want = (compiled(jax.grad(
                        lambda b: jnp.sum(probe_t * dense_collect(b, weight))), buffer),
                    compiled(jax.grad(
                        lambda a: jnp.sum(probe_r * dense_spread(a, scale))), src))
        for g, wnt in zip(got, want):
            np.testing.assert_allclose(g, wnt, rtol=1e-5, atol=1e-6)


def test_a_bounded_buffers_dead_rows_reach_no_value_and_no_gradient(monkeypatch):
    """As the accepted callers' test in ``tests/test_lfm2_moe.py``, at 22
    choices of 512 with 8 held: what the grouped product leaves unwritten is
    NaN here, and nothing reads it."""
    from tests.test_lfm2_moe import _unwritten_rows_are_nan

    k, experts, held = CALLERS["nemotron_h"]
    _, up, down = _kernels(jax.random.PRNGKey(11), held, gated=False)
    x = jax.random.normal(jax.random.PRNGKey(12), (TOKENS, HIDDEN))
    router = jax.random.normal(jax.random.PRNGKey(13), (HIDDEN, experts))

    def layer(x, router, up, down):
        chosen, weights = sigmoid_topk_route(x, router, jnp.zeros((experts,)), k, 5.0)
        out = dropless_experts(x, chosen, weights, None, up, down, held=(0, held),
                               num_experts=experts, activation=relu2)
        return jnp.sum(jnp.sin(out)), out

    def run(*args):  # traced anew each time: the second run meets the patched product
        return compiled(jax.value_and_grad(layer, argnums=range(4), has_aux=True), *args)

    (_, want_out), want = run(x, router, up, down)
    monkeypatch.setattr(dropless, "grouped_matmul", _unwritten_rows_are_nan(dropless.grouped_matmul))
    (_, got_out), got = run(x, router, up, down)
    np.testing.assert_array_equal(got_out, want_out)
    for name, g, w in zip(("x", "router", "up", "down"), got, want):
        assert np.all(np.isfinite(np.asarray(g))), name
        np.testing.assert_array_equal(g, w, err_msg=name)
