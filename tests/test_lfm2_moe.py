"""LFM2-8B-A1B at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights, one chip's share against the
whole expert layer, the gated short convolution against a direct sum over its
taps, the grouped product's tile by shape, and the scopes that name the
model's parts.  The attention kernel's own tests are in
``test_causal_attention.py``, the shared parts' (the attention layer with
normed heads, ``rotate_half``) in ``test_decoder.py``; every comparison here
runs both sides compiled (``helpers.compiled``)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.lfm2_moe import (
    PUBLISHED_LAYER_TYPES,
    Lfm2MoeConfig,
    Lfm2MoeModel,
    RoutedExperts,
    gated_short_conv,
    lfm2_moe_loss_fn,
    lfm2_moe_test_config,
)
from bagua_tpu.observability.scope_grammar import format_model_label
from bagua_tpu.parallel.moe import dropless
from bagua_tpu.parallel.moe.dropless import gmm_tiling, sigmoid_topk_route

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402

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


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(adapter, reference, seed):
    sz = toy_sizes(adapter)
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = Lfm2MoeModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(lfm2_moe_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
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

    grad = compiled(jax.grad(lfm2_moe_loss_fn(model)), params, ids)["embedding"]
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
        want = compiled(lambda h, w: reference.expert_mlp(h, w, whole), h, w)
        routed = jnp.zeros_like(h)
        for share in range(4):  # four chips share the layer: two of the eight experts each
            held = (2 * share, 2)
            cfg = adapter.model_config({**sz, "experts_held": held}, compute_dtype=jnp.float32)
            mine = {k: v[held[0]:held[0] + 2] for k, v in w.items() if k.startswith("e_")}
            params = adapter._block({**w, **mine})["moe"]
            out = compiled(lambda params, h: RoutedExperts(cfg).apply({"params": params}, h),
                           params, h)
            # the reference given the same share gives the same part
            part = compiled(lambda h, w: reference.expert_mlp(
                h, w, {**sz, "experts_held": held}), h, {**w, **mine})
            assert rel_err(out, part) < 1e-5
            routed = routed + out
    assert total == 8 and rel_err(routed, want) < 1e-5
    # and no share alone is the layer: there is no shared expert to carry it
    assert rel_err(out, want) > 0.3


def test_the_routers_eps_is_an_argument_whose_default_is_glms():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros(6)
    chosen, default = compiled(lambda *a: sigmoid_topk_route(*a, 2, 1.0), x, router, bias)
    _, tiny = compiled(lambda *a: sigmoid_topk_route(*a, 2, 1.0, True, 1e-20), x, router, bias)
    _, lfm2 = compiled(lambda *a: sigmoid_topk_route(*a, 2, 1.0, True, 1e-6), x, router, bias)
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
    if dtype == jnp.float32:
        # traced anew each time: the second run meets the patched product.  bf16 stays eager: the
        # two runs are asserted equal bit for bit, and compiled, the NaN-guarded program fuses
        # otherwise than the plain one and the router's gradient moves in its last bit
        run = functools.partial(compiled, run)
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
    got = compiled(gated_short_conv, bcu, taps)
    assert got.dtype == bcu.dtype and rel_err(got, direct_short_conv(bcu, taps)) < 1e-6
    # causal: position 0 sees tap 0 alone, and nothing sees a later position
    first = np.asarray(bcu[:, 0, ch:2 * ch] * taps[0] * bcu[:, 0, :ch] * bcu[:, 0, 2 * ch:])
    np.testing.assert_allclose(got[:, 0], first, rtol=1e-6)
    later = bcu.at[:, 5:].set(7.0)
    np.testing.assert_array_equal(compiled(gated_short_conv, later, taps)[:, :5], got[:, :5])
    # the hand-written backward pass against finite differences of the direct sum
    d_bcu, d_taps = compiled(jax.grad(
        lambda *a: jnp.sum(readout * gated_short_conv(*a)), argnums=(0, 1)), bcu, taps)
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
    got = compiled(gated_short_conv, bcu, taps)
    assert got.dtype == jnp.bfloat16
    exact = direct_short_conv(bcu.astype(jnp.float32), taps)
    np.testing.assert_array_equal(got, jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16))
    _, residuals = jax.vjp(gated_short_conv, bcu, taps)
    kept = sorted((x.shape, str(x.dtype)) for x in jax.tree.leaves(residuals))
    assert kept == sorted([((1, 16, 24), "bfloat16"), ((3, 8), "float32")])
    d_bcu, d_taps = residuals(jnp.ones_like(got))
    assert d_bcu.dtype == jnp.bfloat16 and d_taps.dtype == jnp.float32


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
