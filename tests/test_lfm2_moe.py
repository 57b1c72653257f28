"""LFM2-8B-A1B at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights, one chip's share against the
whole expert layer, the gated short convolution against a direct sum over its
taps, grouped-query attention (the composition and, through Pallas'
interpreter, the chip's kernels) against quadratic attention with repeated
keys, the call with one head count unchanged bit for bit, the grouped
product's tile by shape, and the scopes that name the model's parts."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels import causal_attention as causal_attention_module
from bagua_tpu.kernels.causal_attention import (
    SPLASH_BLOCKS,
    blocked_causal_attention,
    causal_attention,
)
from bagua_tpu.models.lfm2_moe import (
    PUBLISHED_LAYER_TYPES,
    Lfm2MoeConfig,
    Lfm2MoeModel,
    RoutedExperts,
    gated_short_conv,
    lfm2_moe_loss_fn,
    lfm2_moe_test_config,
    rotate_half,
)
from bagua_tpu.observability.scope_grammar import format_model_label
from bagua_tpu.parallel.moe import dropless
from bagua_tpu.parallel.moe.dropless import gmm_tiling, sigmoid_topk_route

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import manifest  # noqa: E402

PARTS = ("conv_proj", "conv_core", "attn_proj", "attn_core", "moe_route", "moe_dispatch",
         "moe_experts", "moe_combine", "dense_mlp", "head")


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/lfm2-8b-a1b.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/lfm2_moe.py")


def toy_sizes(adapter, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: a dense ``conv`` layer, an attention and a ``conv`` expert layer, two
    query heads a key-value head, 2 held of 8 experts, top-2."""
    config = manifest.load_json("benchmark", "configs", "lfm2-8b-a1b.json")
    config = {**config, **config["toy"], **overrides}
    return adapter.sizes(config, {"seq_len": 32})


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(adapter, reference, seed):
    sz = toy_sizes(adapter)
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = Lfm2MoeModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lfm2_moe_loss_fn(model))(
            adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = jax.value_and_grad(reference.loss)(ref_params, ids, sz)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:  # steers the choice only
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), name
        else:
            assert np.linalg.norm(w) > 0, name
            assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    # the one matrix is embedding and output matrix: no second leaf
    assert "lm_head" not in grads and adapter.HEAD_LEAF == "['embedding']"


def test_the_embedding_takes_the_gathers_and_the_heads_gradient(adapter, reference):
    """One leaf, two uses: the head's product gives every row of the slice a
    gradient, and the gather adds its own to the rows that were drawn."""
    sz = toy_sizes(adapter)
    params = adapter.to_program(reference.init_params(jax.random.PRNGKey(5), sz), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(6), 1, sz)
    model = Lfm2MoeModel(adapter.model_config(sz, compute_dtype=jnp.float32))

    grad = jax.grad(lfm2_moe_loss_fn(model))(params, ids)["embedding"]
    drawn = np.zeros(sz["vocab_size"], bool)
    drawn[np.asarray(ids).ravel()] = True
    rows = np.linalg.norm(np.asarray(grad), axis=-1)
    # every row has the head's part; the rows drawn have the gather's on top
    assert np.all(rows > 0) and drawn.sum() < sz["vocab_size"]
    assert np.median(rows[drawn]) > np.median(rows[~drawn])


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    model = Lfm2MoeModel(adapter.model_config(sz))
    ids = adapter.draw_batch(jax.random.PRNGKey(0), 1, sz)
    made = jax.eval_shape(lambda k: model.init(k, ids)["params"], jax.random.PRNGKey(0))
    ref = jax.eval_shape(lambda k: reference.init_params(k, sz), jax.random.PRNGKey(0))
    # marked leaves: each of the reference's lands on exactly one of the program's
    marked = jax.tree.unflatten(jax.tree.structure(ref), [
        jnp.full(leaf.shape, float(n), leaf.dtype) for n, leaf in enumerate(jax.tree.leaves(ref))])
    mapped = adapter.to_program(marked, sz)
    assert jax.tree.structure(mapped) == jax.tree.structure(made)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), mapped) == jax.tree.map(
        lambda x: (x.shape, x.dtype), made)
    assert sorted(float(x.ravel()[0]) for x in jax.tree.leaves(mapped)) == [
        float(n) for n in range(len(jax.tree.leaves(ref)))]
    assert adapter.HEAD_LEAF in {
        jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(made)}


def test_the_config_is_built_from_the_published_keys():
    published = manifest.load_json("benchmark", "configs", "lfm2-8b-a1b.json")
    cfg = Lfm2MoeConfig.from_hf({**published, **published["published"]}, experts_held=(8, 8))
    assert cfg == Lfm2MoeConfig(experts_held=(8, 8))  # the defaults are the published model
    assert (cfg.num_experts, cfg.num_hidden_layers, cfg.vocab_size) == (32, 24, 65536)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size) == (32, 8, 64)
    assert cfg.layer_types == PUBLISHED_LAYER_TYPES and cfg.layer_types.count("conv") == 18
    assert cfg.held == (8, 8) and Lfm2MoeConfig().held == (0, 32)
    with pytest.raises(ValueError, match="is no range"):
        Lfm2MoeConfig(experts_held=(30, 8))
    with pytest.raises(ValueError, match="does not name one of"):
        Lfm2MoeConfig(layer_types=("conv", "window"), num_hidden_layers=2)
    with pytest.raises(ValueError, match="must divide"):
        Lfm2MoeConfig(num_key_value_heads=5)
    assert lfm2_moe_test_config().layer_types == ("conv", "full_attention", "conv")


# -- one chip's share and the whole layer -------------------------------------


def test_the_four_shares_add_up_to_the_uncut_references_layer(adapter, reference):
    sz = toy_sizes(adapter)
    total = sz["routed_experts_total"]
    whole = {**sz, "experts_held": (0, total)}
    w = reference.init_params(jax.random.PRNGKey(5), whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, sz["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.expert_mlp(h, w, whole)
        routed = jnp.zeros_like(h)
        for share in range(4):  # four chips share the layer: two of the eight experts each
            held = (2 * share, 2)
            cfg = adapter.model_config({**sz, "experts_held": held}, compute_dtype=jnp.float32)
            mine = {k: v[held[0]:held[0] + 2] for k, v in w.items() if k.startswith("e_")}
            params = adapter._block({**w, **mine})["moe"]
            out = RoutedExperts(cfg).apply({"params": params}, h)
            # the reference given the same share gives the same part
            part = reference.expert_mlp(h, {**w, **mine}, {**sz, "experts_held": held})
            assert rel_err(out, part) < 1e-5
            routed = routed + out
    assert total == 8 and rel_err(routed, want) < 1e-5
    # and no share alone is the layer: there is no shared expert to carry it
    assert rel_err(out, want) > 0.3


def test_the_routers_eps_is_an_argument_whose_default_is_glms():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros(6)
    chosen, default = sigmoid_topk_route(x, router, bias, 2, 1.0)
    _, tiny = sigmoid_topk_route(x, router, bias, 2, 1.0, True, 1e-20)
    _, lfm2 = sigmoid_topk_route(x, router, bias, 2, 1.0, True, 1e-6)
    np.testing.assert_array_equal(default, tiny)
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(lfm2, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(jnp.max(jnp.abs(lfm2.sum(-1) - 1.0))) < 1e-5 and np.all(lfm2 <= tiny)


def _unwritten_rows_are_nan(grouped_matmul):
    """``grouped_matmul`` as the chip runs it: ``megablox.gmm`` leaves the rows
    past the held groups unwritten, in the product and in its input's
    gradient.  Here they come back NaN."""
    def poison(rows, group_sizes):
        live = (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]
        return jnp.where(live, rows, jnp.nan)

    @jax.custom_vjp
    def poisoned(rows, kernels, group_sizes):
        return poison(grouped_matmul(rows, kernels, group_sizes), group_sizes)

    def fwd(rows, kernels, group_sizes):
        out, vjp = jax.vjp(lambda r, k: grouped_matmul(r, k, group_sizes), rows, kernels)
        return poison(out, group_sizes), (vjp, group_sizes)

    def bwd(res, grad):
        vjp, group_sizes = res
        live = (jnp.arange(grad.shape[0]) < jnp.sum(group_sizes))[:, None]
        d_rows, d_kernels = vjp(jnp.where(live, grad, 0))  # the kernel reads the groups' rows alone
        return poison(d_rows, group_sizes), d_kernels, None

    poisoned.defvjp(fwd, bwd)
    return poisoned


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_rows_the_grouped_product_leaves_unwritten_reach_no_value_and_no_gradient(dtype, monkeypatch):
    tokens, hidden, width, experts, k, held = 64, 16, 8, 32, 4, (8, 8)
    keys = jax.random.split(jax.random.PRNGKey(5), 7)
    x = jax.random.normal(keys[0], (tokens, hidden)).astype(dtype)
    router = 0.5 * jax.random.normal(keys[1], (hidden, experts))
    bias = 0.002 * jax.random.normal(keys[2], (experts,))
    gate, up = (0.3 * jax.random.normal(kk, (held[1], hidden, width)) for kk in keys[3:5])
    down = 0.3 * jax.random.normal(keys[5], (held[1], width, hidden))
    probe = jax.random.normal(keys[6], (tokens, hidden))

    def layer(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, k, 1.0, True, 1e-6)
        out = dropless.dropless_experts(x, chosen, weights, gate, up, down, held=held,
                                        num_experts=experts)
        return jnp.sum(probe * out.astype(jnp.float32)), (out, chosen)

    run = jax.value_and_grad(layer, argnums=range(5), has_aux=True)
    (_, (want_out, chosen)), want = run(x, router, gate, up, down)
    dead = tokens * k - int(jnp.sum((chosen >= held[0]) & (chosen < held[0] + held[1])))
    assert 0 < dead < tokens * k  # a quarter held: most of the buffer is dead rows
    monkeypatch.setattr(dropless, "grouped_matmul", _unwritten_rows_are_nan(dropless.grouped_matmul))
    (_, (got_out, _)), got = run(x, router, gate, up, down)
    np.testing.assert_array_equal(got_out, want_out)
    assert np.all(np.isfinite(np.asarray(got_out, np.float32)))
    for name, g, w in zip(("x", "router", "gate", "up", "down"), got, want):
        assert np.all(np.isfinite(np.asarray(g, np.float32))), name
        np.testing.assert_array_equal(g, w, err_msg=name)


# -- the gated short convolution ----------------------------------------------


def direct_short_conv(bcu, taps):
    """``C_t * sum_j taps[j] * (B * u)_{t-j}``, one position and tap at a time."""
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    b, t, ch = bcu.shape[0], bcu.shape[1], bcu.shape[2] // 3
    gate_b, gate_c, u = bcu[..., :ch], bcu[..., ch:2 * ch], bcu[..., 2 * ch:]
    z = gate_b * u
    out = np.zeros((b, t, ch))
    for pos in range(t):
        for j in range(taps.shape[0]):
            if pos - j >= 0:  # z before position 0 is zero
                out[:, pos] += taps[j] * z[:, pos - j]
    return gate_c * out


@pytest.mark.parametrize("taps_n", [3, 4], ids=["L3", "L4"])
def test_the_short_convolution_equals_a_direct_sum_over_its_taps(taps_n):
    b, t, ch = 2, 9, 5
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    bcu = jax.random.normal(keys[0], (b, t, 3 * ch), jnp.float32)
    taps = jax.random.normal(keys[1], (taps_n, ch), jnp.float32)
    readout = jax.random.normal(keys[2], (b, t, ch), jnp.float32)
    got = gated_short_conv(bcu, taps)
    assert got.dtype == bcu.dtype and rel_err(got, direct_short_conv(bcu, taps)) < 1e-6
    # causal: position 0 sees tap 0 alone, and nothing sees a later position
    first = np.asarray(bcu[:, 0, ch:2 * ch] * taps[0] * bcu[:, 0, :ch] * bcu[:, 0, 2 * ch:])
    np.testing.assert_allclose(got[:, 0], first, rtol=1e-6)
    later = bcu.at[:, 5:].set(7.0)
    np.testing.assert_array_equal(gated_short_conv(later, taps)[:, :5], got[:, :5])
    # the hand-written backward pass against finite differences of the direct sum
    d_bcu, d_taps = jax.grad(lambda *a: jnp.sum(readout * gated_short_conv(*a)), argnums=(0, 1))(bcu, taps)
    eps = 1e-6
    for arg, grad in ((0, d_bcu), (1, d_taps)):
        base = [np.asarray(bcu, np.float64), np.asarray(taps, np.float64)]
        numeric = np.zeros(base[arg].shape)
        for index in np.ndindex(*base[arg].shape):
            up, down = [a.copy() for a in base], [a.copy() for a in base]
            up[arg][index] += eps
            down[arg][index] -= eps
            numeric[index] = np.sum(np.asarray(readout, np.float64) * (
                direct_short_conv(*up) - direct_short_conv(*down))) / (2 * eps)
        assert rel_err(grad, numeric) < 1e-5


def test_the_short_convolution_rounds_once_and_keeps_its_input_alone_for_the_backward_pass():
    bcu = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 3 * 8), jnp.bfloat16)
    taps = jax.random.normal(jax.random.PRNGKey(4), (3, 8), jnp.float32)
    got = gated_short_conv(bcu, taps)
    assert got.dtype == jnp.bfloat16
    exact = direct_short_conv(bcu.astype(jnp.float32), taps)
    np.testing.assert_array_equal(got, jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16))
    _, residuals = jax.vjp(gated_short_conv, bcu, taps)
    kept = sorted((x.shape, str(x.dtype)) for x in jax.tree.leaves(residuals))
    assert kept == sorted([((1, 16, 24), "bfloat16"), ((3, 8), "float32")])
    d_bcu, d_taps = residuals(jnp.ones_like(got))
    assert d_bcu.dtype == jnp.bfloat16 and d_taps.dtype == jnp.float32


def test_rotate_half_pairs_column_i_with_column_i_plus_half():
    t, size, theta = 6, 8, 1e4
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, t, size))
    got = rotate_half(x, theta, 0.5)
    for i in range(size // 2):
        angle = np.arange(t) * theta ** (-2 * i / size)
        a, b = np.asarray(x[..., i]), np.asarray(x[..., i + size // 2])
        np.testing.assert_allclose(got[..., i], 0.5 * (a * np.cos(angle) - b * np.sin(angle)),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(got[..., i + size // 2],
                                   0.5 * (b * np.cos(angle) + a * np.sin(angle)), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[..., 0, :], 0.5 * x[..., 0, :], rtol=1e-6)  # position 0: no turn


# -- grouped-query attention --------------------------------------------------


def quadratic_attention(q, k, v, scale):
    """Every score written down, each key-value head repeated for its group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    t = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def both_passes(attn, q, k, v, d_out):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(d_out.astype(out.dtype))


@pytest.mark.parametrize("block_q", [8, 32, 64])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1), (6, 6)],
                         ids=["4_a_kv_head", "one_kv_head", "one_each"])
def test_blocked_grouped_attention_equals_quadratic_attention_with_repeated_keys(
        heads, kv_heads, block_q):
    b, t, d, scale = 2, 64, 16, 0.25
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d)) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d)) for kk in keys[2:])
    with jax.default_matmul_precision("highest"):
        got = both_passes(lambda *a: blocked_causal_attention(*a, scale, block_q), q, k, v, d_out)
        want = both_passes(lambda *a: quadratic_attention(*a, scale), q, k, v, d_out)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel_err(g, w) < 1e-5
    if block_q == 64:  # off the chip the one entry point is the composition
        np.testing.assert_array_equal(causal_attention(q, k, v, scale), blocked_causal_attention(
            q, k, v, scale, 64))
    with pytest.raises(ValueError, match="heads divide"):
        causal_attention(q, k[:, :1].repeat(5, axis=1), v[:, :1].repeat(5, axis=1), scale)


def _one_head_count_composition(q, k, v, d_out, scale, block_q):
    """The composition as it was before key-value heads (PR 29), forward and
    backward, for the comparison bit for bit."""
    f32 = jnp.float32

    def scores(q_blk, k_seen, start):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_seen, preferred_element_type=f32) * scale
        rows = start + jnp.arange(q_blk.shape[2])[:, None]
        return jnp.where(jnp.arange(k_seen.shape[2])[None, :] <= rows, s, -1e30)

    blocks = [(i * block_q, (i + 1) * block_q) for i in range(q.shape[2] // block_q)]
    outs, lses = [], []
    for start, end in blocks:
        s = scores(q[:, :, start:end], k[:, :, :end], start)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :end],
                       preferred_element_type=f32) / l
        outs.append(o.astype(q.dtype))
        lses.append((m + jnp.log(l))[..., 0])
    out, lse = jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1, keepdims=True)
    dq, dk, dv = [], jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    for start, end in blocks:
        q_blk, do_blk = q[:, :, start:end], d_out[:, :, start:end]
        p = jnp.exp(scores(q_blk, k[:, :, :end], start) - lse[:, :, start:end, None])
        dv = dv.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(v.dtype), do_blk, preferred_element_type=f32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, v[:, :, :end], preferred_element_type=f32)
        ds = (p * (dp - delta[:, :, start:end]) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhqk,bhkd->bhqd", ds, k[:, :, :end],
                             preferred_element_type=f32).astype(q.dtype))
        dk = dk.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_blk, preferred_element_type=f32))
    return out, jnp.concatenate(dq, axis=2), dk.astype(k.dtype), dv.astype(v.dtype)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_call_with_one_head_count_is_unchanged_bit_for_bit(dtype, jitted):
    """``models/glm_moe.py`` calls with as many key-value heads as query
    heads: its result and its three gradients are the bits they were."""
    b, h, t, d, scale, block_q = 2, 3, 128, 32, 0.17, 32
    q, k, v, d_out = (jax.random.normal(kk, (b, h, t, d), dtype)
                      for kk in jax.random.split(jax.random.PRNGKey(0), 4))

    def now(q, k, v, d_out):
        return both_passes(lambda *a: blocked_causal_attention(*a, scale, block_q), q, k, v, d_out)

    def before(q, k, v, d_out):
        return _one_head_count_composition(q, k, v, d_out, scale, block_q)

    if jitted:
        now, before = jax.jit(now), jax.jit(before)
    for got, want in zip(now(q, k, v, d_out), before(q, k, v, d_out)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_the_chips_kernels_serve_four_query_heads_a_key_value_head_in_interpret_mode(dtype):
    """The TPU branch at a head of 64 through Pallas' interpreter: 8 query
    heads on 2 key-value heads, two tiles of positions, no key repeated;
    ``dK`` and ``dV`` are sums over each group inside the kernel."""
    b, heads, kv_heads, d = 1, 8, 2, 64
    t = 2 * causal_attention_module.SPLASH_BLOCK_MAJOR
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d), dtype) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d), dtype) for kk in keys[2:])

    def f32(x):
        return x.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        got = both_passes(lambda q, k, v: causal_attention_module._splash_causal_attention(
            q, k, v, 0.125, interpret=True), q, k, v, d_out)
        want = both_passes(lambda q, k, v: quadratic_attention(f32(q), f32(k), f32(v), 0.125),
                           q, k, v, d_out)
    near = 1e-5 if dtype == jnp.float32 else 6e-3
    for g, w, like in zip(got, want, (q, q, k, v)):
        assert g.dtype == dtype and g.shape == like.shape
        assert rel_err(g, w) < near


def test_a_group_goes_to_the_multi_query_kernels_and_one_head_count_to_what_it_had():
    build = causal_attention_module._splash_kernel
    major = causal_attention_module.SPLASH_BLOCK_MAJOR
    assert SPLASH_BLOCKS == dict(block_q=1024, block_kv=1024, block_kv_compute=256,
                                 block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512)
    grouped = build(4, major, True, multi_query=True)
    assert grouped is build(4, major, True, multi_query=True) and grouped is not build(4, major, True)
    assert grouped.kwargs["is_mqa"] and not build(4, major, True).kwargs["is_mqa"]

    def kernels(q_heads, kv_heads):
        q = jax.ShapeDtypeStruct((1, q_heads, major, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, kv_heads, major, 64), jnp.bfloat16)
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            causal_attention_module._splash_causal_attention(q, k, v, 1.0, interpret=True).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, kv, kv))

    # one forward and one fused backward kernel either way; no key is repeated
    for text, name in ((kernels(8, 2), "splash_mqa"), (kernels(2, 2), "splash_mha")):
        assert text.count("pallas_call") == 2 and name + "_fwd" in text and name + "_dkv" in text
    assert "splash_mha" not in kernels(8, 2) and "splash_mqa" not in kernels(2, 2)


# -- the grouped product's tile -----------------------------------------------


@pytest.mark.parametrize("width,tile", [(1536, (512, 1024, 768)), (1792, (128, 0, 896)),
                                        (1024, (512, 1024, 512))])
def test_the_grouped_products_tile_is_chosen_by_shape(width, tile):
    """``megablox`` asks for the forward product (hidden to width, width to
    hidden) and for the two products of its backward pass, each with its own
    contraction and columns: one tile an expert width, half of it across."""
    rows, hidden = 32768, 2048
    for k, n in ((hidden, width), (width, hidden)):  # a contraction of 0: all of it in one tile
        assert gmm_tiling(rows, k, n) == (tile[0], tile[1] or k, tile[2])
    assert width % tile[2] == 0 and tile[2] % 128 == 0
    # a toy width: one tile of lanes against the whole contraction (1,024 does not divide it)
    assert gmm_tiling(64, 48, 24) == (512, 48, 128)


# -- the scopes ---------------------------------------------------------------


def test_every_part_is_named_in_both_passes():
    cfg = lfm2_moe_test_config()
    model = Lfm2MoeModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embedding", "final_norm", "layer_0", "layer_1", "layer_2"}
    assert set(params["layer_0"]) == {"operator_norm", "conv", "ffn_norm", "mlp"}
    assert set(params["layer_1"]) == {"operator_norm", "attn", "ffn_norm", "moe"}
    assert set(params["layer_2"]) == {"operator_norm", "conv", "ffn_norm", "moe"}
    assert "shared" not in params["layer_1"]["moe"]
    text = jax.jit(jax.grad(lfm2_moe_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    assert format_model_label("moe_shared") not in text
