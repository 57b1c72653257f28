"""SmallThinker-21BA3B at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights, once per kind of layer; the
softmax router against a one-line oracle; the ReLU gate at six choices a token;
one chip's share against the whole expert layer; the grouped product's tile at
width 768 of 2,560; and the scopes that name the model's parts.  The attention
kernel under a window has its tests in ``test_causal_attention.py``, the shared
attention layer (with and without positions, with and without a window) in
``test_decoder.py``; every comparison here runs both sides compiled
(``helpers.compiled``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.smallthinker_moe import (
    PUBLISHED_LAYOUT,
    SmallThinkerBlock,
    SmallThinkerConfig,
    SmallThinkerModel,
    smallthinker_loss_fn,
    smallthinker_test_config,
)
from bagua_tpu.observability import trace_analysis as ta
from bagua_tpu.observability.scope_grammar import format_model_label, parse_model_part
from bagua_tpu.parallel.moe.dropless import (
    GMM_TILES,
    dropless_experts,
    gmm_tiling,
    softmax_topk_route,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "ci"))
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402
from trim_capture import xspace_bytes  # noqa: E402

PARTS = ("attn_proj", "attn_core", "attn_window_core", "moe_route", "moe_dispatch",
         "moe_experts", "moe_combine", "head")
#: ``(sliding_window_layout, rope_layout)`` of a toy model: each kind alone,
#: the published pair of kinds, and the two keys apart (a window without
#: positions, positions without a window), which the published model never has
LAYOUTS = {"global": ((0,), (0,)), "window": ((1,), (1,)), "period": ((0, 1), (0, 1)),
           "keys_apart": ((1, 0), (0, 1))}


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/smallthinker-21ba3b.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/smallthinker_moe.py")


def toy_sizes(adapter, layouts=None, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: a global and a windowed layer, seven query heads a key-value head,
    a window of 24 keys, 2 held of 8 experts, top-3."""
    config = manifest.load_json("benchmark", "configs", "smallthinker-21ba3b.json")
    config = {**config, **config["toy"], **overrides}
    if layouts is not None:
        config.update(sliding_window_layout=list(layouts[0]), rope_layout=list(layouts[1]),
                      num_hidden_layers=len(layouts[0]))
    return adapter.sizes(config, {"seq_len": 32})


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("layouts", sorted(LAYOUTS))
@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(
        adapter, reference, seed, layouts):
    sz = toy_sizes(adapter, LAYOUTS[layouts])
    assert sz["sliding_window_size"] < sz["seq_len"]  # the window hides keys
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = SmallThinkerModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(smallthinker_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert np.linalg.norm(w) > 0, name
        assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    # the output matrix is a leaf of its own
    assert adapter.HEAD_LEAF == "['lm_head']" and grads["lm_head"].shape == (
        sz["hidden_size"], sz["vocab_size"])


def test_the_window_and_the_positions_change_the_loss(adapter, reference):
    """The four kinds of layer are four functions: no key is ignored."""
    sz = toy_sizes(adapter, LAYOUTS["global"])
    params = reference.init_params(jax.random.PRNGKey(0), sz)
    # the seeded output matrix is small beside the embedding: large enough here to read in the loss
    params["layers"][0]["w_o"] = 100.0 * params["layers"][0]["w_o"]
    ids = adapter.draw_batch(jax.random.PRNGKey(1), 2, sz)
    losses = set()
    for window in (0, 1):
        for rope in (0, 1):
            kind = {**sz, "sliding_window_layout": (window,), "rope_layout": (rope,)}
            model = SmallThinkerModel(adapter.model_config(kind, compute_dtype=jnp.float32))
            got = float(compiled(smallthinker_loss_fn(model), adapter.to_program(params, kind), ids))
            assert got == pytest.approx(float(compiled(
                lambda p, ids: reference.loss(p, ids, kind), params, ids)), abs=2e-5)
            losses.add(round(got, 5))
    assert len(losses) == 4


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    model = SmallThinkerModel(adapter.model_config(sz))
    ids = adapter.draw_batch(jax.random.PRNGKey(0), 1, sz)
    made = jax.eval_shape(lambda k: model.init(k, ids)["params"], jax.random.PRNGKey(0))
    ref = jax.eval_shape(lambda k: reference.init_params(k, sz), jax.random.PRNGKey(0))
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
    published = manifest.load_json("benchmark", "configs", "smallthinker-21ba3b.json")
    cfg = SmallThinkerConfig.from_hf({**published, **published["published"]}, experts_held=(8, 8))
    assert cfg == SmallThinkerConfig(experts_held=(8, 8))  # the defaults are the published model
    assert (cfg.moe_num_primary_experts, cfg.num_hidden_layers, cfg.vocab_size) == (64, 52, 151936)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (28, 4, 128)
    assert cfg.sliding_window_layout == cfg.rope_layout == PUBLISHED_LAYOUT == (0, 1, 1, 1) * 13
    assert cfg.held == (8, 8) and SmallThinkerConfig().held == (0, 64)
    with pytest.raises(ValueError, match="is no range"):
        SmallThinkerConfig(experts_held=(60, 8))
    with pytest.raises(ValueError, match="is no 0 or 1 for each"):
        SmallThinkerConfig(rope_layout=(0, 1, 2), sliding_window_layout=(0, 1, 1), num_hidden_layers=3)
    with pytest.raises(ValueError, match="must divide"):
        SmallThinkerConfig(num_key_value_heads=5)
    with pytest.raises(NotImplementedError):
        SmallThinkerConfig(tie_word_embeddings=True)
    toy = smallthinker_test_config()
    assert toy.sliding_window_layout == toy.rope_layout == (0, 1)


# -- the router ---------------------------------------------------------------


def test_the_softmax_router_is_a_softmax_over_the_largest_logits():
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    chosen, weights = compiled(lambda h, w: softmax_topk_route(h, w, 6), h.astype(jnp.bfloat16), w)
    with jax.default_matmul_precision("highest"):
        top, want = compiled(lambda h, w: jax.lax.top_k(
            h.astype(jnp.bfloat16).astype(jnp.float32) @ w, 6), h, w)
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_allclose(weights, jax.nn.softmax(top, axis=-1), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, rtol=1e-6)
    assert np.all(np.diff(np.asarray(weights), axis=-1) <= 0)  # largest first
    # the weights' gradient reaches the router through the chosen logits alone
    grad = compiled(jax.grad(lambda w: jnp.sum(softmax_topk_route(h, w, 6)[1][:, 0])), w)
    assert np.linalg.norm(grad) > 0


def test_equal_logits_go_to_the_lower_index_and_share_the_weight():
    # four experts with one logit, then two below: top-3 takes experts 0, 1, 2
    router = jnp.array([[1.0, 1.0, 1.0, 1.0, 0.5, 0.0]])
    chosen, weights = softmax_topk_route(jnp.ones((5, 1)), router, 3)
    np.testing.assert_array_equal(chosen, np.tile([0, 1, 2], (5, 1)))
    np.testing.assert_allclose(weights, 1 / 3, rtol=1e-6)
    chosen, weights = softmax_topk_route(jnp.ones((5, 1)), router, 5)
    np.testing.assert_array_equal(chosen[0], [0, 1, 2, 3, 4])
    assert float(weights[0, 3]) == pytest.approx(float(weights[0, 0]))
    assert float(weights[0, 3]) > float(weights[0, 4])


# -- the expert layer at six choices and a ReLU gate --------------------------


def dense_experts(x, chosen, weights, gate, up, down, first, activation):
    """Every held expert on every token, under its weight."""
    total = jnp.zeros_like(x)
    for e in range(gate.shape[0]):
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1, keepdims=True)
        total = total + weight * ((activation(x @ gate[e]) * (x @ up[e])) @ down[e])
    return total


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (14, 2)], ids=["all", "middle", "last"])
def test_the_relu_gate_at_six_choices_a_token_equals_every_expert_on_every_token(held):
    tokens, hidden, width, experts, fan = 48, 32, 16, 16, 6
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(keys[0], (tokens, hidden))
    chosen, weights = compiled(lambda x, router: softmax_topk_route(x, router, fan),
                               x, jax.random.normal(keys[1], (hidden, experts)))
    gate, up = (jax.random.normal(kk, (held[1], hidden, width)) * 0.2 for kk in keys[2:4])
    down = jax.random.normal(keys[4], (held[1], width, hidden)) * 0.2
    d_out = jax.random.normal(keys[5], (tokens, hidden))

    def layer(activation):
        return lambda x, weights, gate, up, down: dropless_experts(
            x, chosen, weights, gate, up, down, held=held, num_experts=experts,
            **({} if activation is None else {"activation": activation}))

    def oracle(activation):
        return lambda x, weights, gate, up, down: dense_experts(
            x, chosen, weights, gate, up, down, held[0], activation)

    args = (x, weights, gate, up, down)

    def passes(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(d_out)
        return compiled(run, *args)

    with jax.default_matmul_precision("highest"):
        got, want = passes(layer(jax.nn.relu)), passes(oracle(jax.nn.relu))
        silu = compiled(layer(None), *args)
        silu_want = compiled(oracle(jax.nn.silu), *args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel_err(g, w) < 1e-5
    # the default gate is SiLU still, and the two gates are two functions
    assert rel_err(silu, silu_want) < 1e-5 and rel_err(silu, want[0]) > 0.1


def test_the_default_gate_traces_to_the_program_it_was():
    x = jnp.ones((8, 4))
    chosen = jnp.zeros((8, 2), jnp.int32).at[:, 1].set(1)
    args = (x, chosen, jnp.ones((8, 2)) / 2, jnp.ones((2, 4, 3)), jnp.ones((2, 4, 3)),
            jnp.ones((2, 3, 4)))

    def text(**kw):
        return str(jax.make_jaxpr(lambda *a: dropless_experts(
            *a, held=(0, 2), num_experts=4, **kw))(*args))

    assert text() == text(activation=jax.nn.silu) != text(activation=jax.nn.relu)
    assert "logistic" in text() and "logistic" not in text(activation=jax.nn.relu)


# -- one chip's share and the whole layer -------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_references_layer(adapter, reference):
    """Eight chips share the layer, one of the toy's eight experts each (the
    cell: 8 of 64): each share's routed result is the reference's for that
    share, and the eight add up to the whole layer's."""
    sz = toy_sizes(adapter, LAYOUTS["window"], moe_num_primary_experts=1)
    total = sz["routed_experts_total"]
    whole = {**sz, "experts_held": (0, total)}
    w = reference.init_params(jax.random.PRNGKey(5), whole)["layers"][0]
    # experts large enough that their part is read off the residual stream to seven digits
    w = {k: 5.0 * v if k.startswith("e_") else v for k, v in w.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    eps = sz["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        # the reference's layer, taken apart: what attention adds, then the routed part
        def taken_apart(x, w):
            chosen, picked = reference.route(reference.rms_norm(x, w["norm_in"], eps), w, whole)
            x1 = x + reference.attention(
                reference.rms_norm(x, w["norm_in"], eps), w, whole, True, True)
            want = reference.experts(
                reference.rms_norm(x1, w["norm_post"], eps), chosen, picked, w, whole)
            late, _ = reference.route(reference.rms_norm(x1, w["norm_post"], eps), w, whole)
            return chosen, x1, want, late

        chosen, x1, want, late = compiled(taken_apart, x, w)
        assert rel_err(x1 + want, compiled(
            lambda x, w: reference.layer(x, w, whole, True, True), x, w)) < 1e-6
        routed = jnp.zeros_like(x)
        for share in range(total):
            held = (share, 1)
            mine = {k: v[share:share + 1] for k, v in w.items() if k.startswith("e_")}
            cfg = adapter.model_config({**sz, "experts_held": held}, compute_dtype=jnp.float32)
            out = compiled(lambda params, x: SmallThinkerBlock(cfg, True, True).apply(
                {"params": params}, x), adapter._block({**w, **mine}), x)
            part = compiled(lambda x, w: reference.layer(
                x, w, {**sz, "experts_held": held}, True, True), x, {**w, **mine})
            assert rel_err(out, part) < 1e-5
            routed = routed + (out - x1)
    assert total == 8 and rel_err(routed, want) < 1e-5
    # no share alone is the layer, and the router chose before attention: from norm_in(x)
    assert rel_err(out - x1, want) > 0.3
    assert np.mean(np.asarray(late) != np.asarray(chosen)) > 0.05


# -- the grouped product's tile -----------------------------------------------


def test_the_grouped_products_tile_at_width_768_of_2560_divides():
    """``megablox`` asks for ``(rows, 2560, 768)`` forward and for its two
    transposes in the backward pass: whole tiles of lanes that divide the
    contraction (a tile that hangs over is masked in float32 at every step)
    and the columns."""
    assert (2560, 768) in GMM_TILES and (768, 2560) in GMM_TILES and 768 not in GMM_TILES
    for k, n in ((2560, 768), (768, 2560)):
        rows, contraction, columns = gmm_tiling(49152, k, n)
        assert k % contraction == 0 and (contraction % 128 == 0 or contraction == k), (k, n)
        assert columns % 128 == 0 and n % columns == 0, (k, n, columns)
        assert rows % 8 == 0 and 49152 % rows == 0


@pytest.mark.parametrize("k,n,tile", [
    (2560, 1024, (512, 512, 512)),    # 1,024 does not divide 2,560: the most that does
    (1024, 2560, (512, 1024, 512)),
    (2048, 1024, (512, 1024, 512)),   # what it gave where 1,024 divides
    (1920, 640, (512, 128, 128)),     # 15 x 128: one tile of lanes
    (48, 24, (512, 48, 128)),         # a toy: the whole contraction
])
def test_an_unmeasured_width_takes_a_contraction_tile_that_divides(k, n, tile):
    assert min(k, n) not in GMM_TILES
    assert gmm_tiling(32768, k, n) == tile and k % tile[1] == 0


# -- the scopes ---------------------------------------------------------------


def test_every_part_is_named_in_both_passes_and_the_router_comes_first():
    cfg = smallthinker_test_config()
    model = SmallThinkerModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embedding", "final_norm", "lm_head", "layer_0", "layer_1"}
    assert set(params["layer_0"]) == {"input_norm", "router", "attn", "post_attention_norm",
                                      "experts_gate", "experts_up", "experts_down"}
    assert set(params["layer_0"]["attn"]) == {"q_proj", "k_proj", "v_proj", "out_proj"}
    text = jax.jit(jax.grad(smallthinker_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    for absent in ("moe_shared", "dense_mlp", "conv_core"):
        assert format_model_label(absent) not in text
    # in a layer's order the router stands before the attention it no longer waits for, and the
    # global layer's core and the windowed layer's are named apart
    forward = [parse_model_part(str(eqn.source_info.name_stack)) for eqn in jax.make_jaxpr(
        smallthinker_loss_fn(model))(params, ids).eqns]
    order = [p for p, before in zip(forward[1:], forward) if p and p != before]
    layer = ["moe_route", "attn_proj", "{core}", "attn_proj", "moe_dispatch", "moe_experts",
             "moe_combine"]
    assert order == ([p.format(core="attn_core") for p in layer]
                     + [p.format(core="attn_window_core") for p in layer] + ["head"])


def test_the_summary_reads_the_two_cores_apart(tmp_path):
    """``model_part_ms`` keeps what it finds: the windowed core beside the
    global one, with nothing changed for a model that names neither."""
    fwd, bwd = "bagua_step/phase=fwd_bwd", "bagua_step/phase=fwd_bwd/transpose(jvp(m))"
    ops = []

    def op(n, start, end, op_name):
        ops.append((f"%fusion.{n} = f32[4] fusion()", 1000 * (1000 + start), 1000 * (end - start),
                    {"op_name": op_name}))

    op(1, 0, 10, fwd + "/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(2, 10, 16, fwd + "/layer_1/attn/bagua_model/part=attn_window_core/pallas_call")
    op(3, 16, 28, bwd + "/layer_1/attn/bagua_model/part=attn_window_core/pallas_call")
    op(4, 28, 48, bwd + "/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(5, 48, 50, bwd + "/layer_0/add")
    modules = [("jit_local_step(1)", 1000 * 1000, 1000 * 50, {})]
    path = str(tmp_path / "cores.xplane.pb")
    with open(path, "wb") as f:
        f.write(xspace_bytes([("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, ops)])]))
    got = ta.summarize_capture(path)
    ms = pytest.approx
    assert got["model_part_ms"] == {"attn_core": ms(0.030), "attn_window_core": ms(0.018),
                                    "other": ms(0.002)}
    assert got["partition_ms"] == {"forward": ms(0.016), "backward": ms(0.034)}
