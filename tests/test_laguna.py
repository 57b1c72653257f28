"""Laguna-XS.2 at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights (forward, loss, every gradient
leaf); one chip's share against the whole expert layer, the shared expert and
attention counted once; the YaRN tables against the closed form at the
published keys; two head counts and two rotary tables in one stack; the gate
read from the normed input; the grouped product's tile at width 512 of 2,048;
and the scopes that name the model's parts.  The attention layer's tables and
gate have their tests in ``test_decoder.py``, the kernel's edges by mask in
``test_causal_attention.py``; every comparison here runs both sides compiled
(``helpers.compiled``)."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.decoder import RotaryTables
from bagua_tpu.models.laguna import (
    FULL,
    PUBLISHED_HEADS,
    PUBLISHED_LAYER_TYPES,
    SLIDING,
    LagunaBlock,
    LagunaConfig,
    LagunaModel,
    RopeParameters,
    laguna_loss_fn,
    laguna_test_config,
    yarn_inv_freq,
)
from bagua_tpu.observability.scope_grammar import format_model_label, parse_model_part
from bagua_tpu.parallel.moe.dropless import GMM_TILES, gmm_tiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402

PARTS = ("embed", "attn_proj", "attn_gate", "attn_core", "attn_window_core", "dense_mlp",
         "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "head")


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/laguna-xs.2.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/laguna.py")


def toy_sizes(adapter):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: a global layer of 4 query heads over the dense MLP, a windowed layer
    of 6 and a global one of 4 over experts, a window of 24 keys, 4 held of 16
    experts, top-3."""
    config = manifest.load_json("benchmark", "configs", "laguna-xs.2.json")
    config = {**config, **config["toy"]}
    return adapter.sizes(config, {"seq_len": 32})


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(adapter, reference, seed):
    sz = toy_sizes(adapter)
    assert sz["sliding_window"] < sz["seq_len"]  # the window hides keys
    assert len(set(sz["num_attention_heads_per_layer"])) == 2 and set(sz["layer_types"]) == {
        FULL, SLIDING}
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = LagunaModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(laguna_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=4e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "correction_bias" in name:  # steers the choice and takes no gradient
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w))
            continue
        assert np.linalg.norm(w) > 0, name
        assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    assert adapter.HEAD_LEAF == "['lm_head']" and grads["lm_head"].shape == (
        sz["hidden_size"], sz["vocab_size"])


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    model = LagunaModel(adapter.model_config(sz))
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
    # two head counts in one stack: two shapes of q, o and gate, one of k and v
    heads, size, hidden = sz["num_attention_heads_per_layer"], sz["head_dim"], sz["hidden_size"]
    for n, count in enumerate(heads):
        attn = made[f"layer_{n}"]["attn"]
        assert attn["q_proj"].shape == (hidden, count * size) == attn["out_proj"].shape[::-1]
        assert attn["gate_proj"].shape == (hidden, count)
        assert attn["k_proj"].shape == attn["v_proj"].shape == (
            hidden, sz["num_key_value_heads"] * size)
    assert set(made["layer_0"]) == {"input_norm", "attn", "post_attention_norm", "mlp"}
    assert set(made["layer_1"]) == {"input_norm", "attn", "post_attention_norm", "moe"}


def test_the_config_is_built_from_the_published_keys():
    published = manifest.load_json("benchmark", "configs", "laguna-xs.2.json")
    cfg = LagunaConfig.from_hf({**published, **published["published"]}, experts_held=(32, 32))
    assert cfg == LagunaConfig(experts_held=(32, 32))  # the defaults are the published model
    assert (cfg.num_experts, cfg.num_hidden_layers, cfg.vocab_size) == (256, 40, 100352)
    assert cfg.layer_types == PUBLISHED_LAYER_TYPES == (FULL, SLIDING, SLIDING, SLIDING) * 10
    assert cfg.num_attention_heads_per_layer == PUBLISHED_HEADS == (48, 64, 64, 64) * 10
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 39
    assert cfg.held == (32, 32) and LagunaConfig().held == (0, 256)
    assert cfg.rotary(SLIDING) == {"rope_theta": 10000}
    tables = cfg.rotary(FULL)["rope"]
    assert tables.columns == 64 and tables.factor == 1.4158883083359672
    with pytest.raises(ValueError, match="is no range"):
        LagunaConfig(experts_held=(250, 8))
    with pytest.raises(ValueError, match="is no one of"):
        LagunaConfig(layer_types=("full_attention", "linear"), num_hidden_layers=2,
                     mlp_layer_types=("dense", "sparse"), num_attention_heads_per_layer=(48, 64))
    with pytest.raises(ValueError, match="no multiple of"):
        LagunaConfig(num_attention_heads_per_layer=(48, 60, 64, 64) * 10)
    with pytest.raises(NotImplementedError):
        LagunaConfig(tie_word_embeddings=True)
    with pytest.raises(NotImplementedError):
        RopeParameters(rope_type="longrope")
    toy = laguna_test_config()
    assert toy.layer_types == (FULL, SLIDING, FULL) and toy.num_attention_heads_per_layer == (4, 6, 4)


# -- the rotary tables --------------------------------------------------------


def test_the_yarn_table_is_the_closed_form_with_low_5_and_high_16_at_the_published_keys():
    inv_freq, low, high = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16) and len(inv_freq) == 32
    # c(m) = 64 ln(4096 / (2 pi m)) / (2 ln 500000): 5.66 at 64 turns, 15.80 at one
    c = [64 * math.log(4096 / (2 * math.pi * m)) / (2 * math.log(500000.0)) for m in (64, 1)]
    assert c == pytest.approx([5.66, 15.80], abs=0.01)
    for i, got in enumerate(inv_freq):
        plain = 500000.0 ** (-2 * i / 64)
        r = 1 - min(max((i - 5) / 11, 0.0), 1.0)
        assert got == pytest.approx((1 - r) * plain / 64 + r * plain, rel=1e-12), i
    # the fast pairs turn as they did, the slow ones 64 times slower, the ramp between
    assert inv_freq[:6] == tuple(500000.0 ** (-2 * i / 64) for i in range(6))
    assert inv_freq[16:] == pytest.approx([500000.0 ** (-2 * i / 64) / 64 for i in range(16, 32)])
    assert all(a > b for a, b in zip(inv_freq, inv_freq[1:]))
    # and the program's layer takes exactly these, with the published factor
    tables = LagunaConfig().rotary(FULL)["rope"]
    assert tables == RotaryTables(inv_freq, 1.4158883083359672)
    assert 0.1 * math.log(64.0) + 1.0 == pytest.approx(tables.factor, rel=1e-12)


def test_the_reference_computes_the_same_tables_on_its_own(reference):
    rope = manifest.load_json("benchmark", "configs", "laguna-xs.2.json")["rope_parameters"]
    got, factor = reference.inv_freq(rope[FULL], 64)
    want, _, _ = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert factor == 1.4158883083359672
    got, factor = reference.inv_freq(rope[SLIDING], 128)
    np.testing.assert_allclose(got, 10000.0 ** (-np.arange(64) / 64), rtol=2e-6)
    assert factor == 1.0


# -- the gate and one layer of each kind --------------------------------------


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["global_dense", "window_experts",
                                                   "global_experts"])
def test_a_layer_is_the_references_and_its_gate_reads_the_normed_input(adapter, reference, layer):
    sz = toy_sizes(adapter)
    w = reference.init_params(jax.random.PRNGKey(5), sz)["layers"][layer]
    # a gate and an output large enough to be read off the residual stream
    w = {**w, "w_g": 8.0 * w["w_g"], "w_o": 30.0 * w["w_o"]}
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    cfg = adapter.model_config(sz, compute_dtype=jnp.float32)
    kind = sz["layer_types"][layer]
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda params, x: LagunaBlock(cfg, layer).apply({"params": params}, x),
                       adapter._block(w), x)
        want = compiled(lambda x, w: reference.layer(x, w, sz, kind), x, w)

        def gated_from(gate_input):
            """The layer with ``a = sigmoid(gate_input W_g)``, head by head: the ungated
            core of one head through its rows of ``W_o`` (the reference with ``W_g`` zero
            gives half of it), times that head's scalar."""
            def fn(x, w):
                eps, size = sz["rms_norm_eps"], sz["head_dim"]
                heads = w["w_q"].shape[1] // size
                h = reference.rms_norm(x, w["norm_in"], eps)
                a = jax.nn.sigmoid(gate_input(x, h) @ w["w_g"])
                x1 = x
                for j in range(heads):
                    rows = (jnp.arange(heads * size) // size == j)[:, None]
                    half = reference.attention(h, {
                        **w, "w_g": jnp.zeros_like(w["w_g"]),
                        "w_o": jnp.where(rows, w["w_o"], 0.0)}, sz, kind)
                    x1 = x1 + 2.0 * half * a[..., j:j + 1]
                return x1 + reference.mlp(reference.rms_norm(x1, w["norm_post"], eps), w, sz)
            return fn

        from_normed = compiled(gated_from(lambda x, h: h), x, w)
        from_stream = compiled(gated_from(lambda x, h: x), x, w)
    assert rel_err(got, want) < 1e-5 and rel_err(got, from_normed) < 1e-5
    assert rel_err(got, from_stream) > 1e-3  # the two inputs give two layers


# -- one chip's share and the whole layer -------------------------------------


def test_the_four_shares_add_up_to_the_uncut_references_layer(adapter, reference):
    """Four chips share the toy's layer, 4 of its 16 experts each (the cell: 32
    of 256 on each of 8): each share's result is the reference's for that
    share, and the routed parts add up to the whole layer's, with attention and
    the shared expert, which every chip computes alike, counted once."""
    sz = toy_sizes(adapter)
    total, held = sz["routed_experts_total"], sz["experts_held"][1]
    whole = {**sz, "experts_held": (0, total)}
    layer, kind = 1, sz["layer_types"][1]
    w = reference.init_params(jax.random.PRNGKey(5), whole)["layers"][layer]
    assert w["e_gate"].shape[0] == total == 16 and held == 4
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    eps = sz["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        def taken_apart(x, w):
            x1 = x + reference.attention(reference.rms_norm(x, w["norm_in"], eps), w, whole, kind)
            u = reference.rms_norm(x1, w["norm_post"], eps)
            chosen, picked = reference.route(u, w, whole)
            shared = reference.swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
            return x1, shared, reference.routed_experts(u, chosen, picked, w, whole)

        x1, shared, routed_want = compiled(taken_apart, x, w)
        assert rel_err(x1 + shared + routed_want, compiled(
            lambda x, w: reference.layer(x, w, whole, kind), x, w)) < 1e-6
        routed = jnp.zeros_like(x)
        for share in range(total // held):
            mine = {k: v[share * held:(share + 1) * held] for k, v in w.items()
                    if k.startswith("e_")}
            here = {**sz, "experts_held": (share * held, held)}
            cfg = adapter.model_config(here, compute_dtype=jnp.float32)
            out = compiled(lambda params, x: LagunaBlock(cfg, layer).apply({"params": params}, x),
                           adapter._block({**w, **mine}), x)
            part = compiled(lambda x, w: reference.layer(x, w, here, kind), x, {**w, **mine})
            assert rel_err(out, part) < 1e-5
            routed = routed + (out - x1 - shared)
    assert rel_err(routed, routed_want) < 1e-5
    # no share alone is the layer, and the routed part is a part one can read
    assert rel_err(out - x1 - shared, routed_want) > 0.3
    assert np.linalg.norm(routed_want) > 0.05 * np.linalg.norm(shared)


# -- thirty-two groups in one buffer ------------------------------------------


def test_32_held_of_256_at_8_choices_equal_every_held_expert_on_every_token():
    """The cell's routing at a toy width: ``sigmoid_topk_route`` over 256 outputs
    and 8 choices, 32 groups in a buffer of ``tokens x 8`` rows of which an eighth
    is live, against each held expert applied to every token under its weight, in
    value and every gradient.  The layer's other callers hold 8 groups or fewer."""
    from bagua_tpu.parallel.moe.dropless import dropless_experts, sigmoid_topk_route

    tokens, hidden, width, experts, fan, held = 96, 16, 8, 256, 8, (64, 32)
    keys = jax.random.split(jax.random.PRNGKey(2), 7)
    x = jax.random.normal(keys[0], (tokens, hidden))
    router = jax.random.normal(keys[1], (hidden, experts))
    bias = 0.002 * jax.random.normal(keys[2], (experts,))
    gate, up = (0.3 * jax.random.normal(kk, (held[1], hidden, width)) for kk in keys[3:5])
    down = 0.3 * jax.random.normal(keys[5], (held[1], width, hidden))
    d_out = jax.random.normal(keys[6], (tokens, hidden))

    def layer(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, fan, 2.5)
        return dropless_experts(x, chosen, weights, gate, up, down, held=held, num_experts=experts)

    def oracle(x, router, gate, up, down):
        scores = jax.nn.sigmoid(x @ router)
        _, chosen = jax.lax.top_k(scores + bias, fan)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        picked = 2.5 * picked / jnp.sum(picked, axis=-1, keepdims=True)
        total = jnp.zeros_like(x)
        for e in range(held[1]):
            w = jnp.sum(jnp.where(chosen == held[0] + e, picked, 0.0), axis=-1, keepdims=True)
            total = total + w * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
        return total, jnp.sum((chosen >= held[0]) & (chosen < held[0] + held[1]))

    def passes(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(d_out)
        return run

    args = (x, router, gate, up, down)
    with jax.default_matmul_precision("highest"):
        got = compiled(passes(layer), *args)
        want = compiled(passes(lambda *a: oracle(*a)[0]), *args)
        live = int(compiled(lambda *a: oracle(*a)[1], *args))
    for g, w in zip(got, want):
        assert g.shape == w.shape and np.linalg.norm(w) > 0 and rel_err(g, w) < 1e-5
    assert 0 < live < tokens * fan // 4  # near an eighth of the buffer's rows is live


# -- the grouped product's tile -----------------------------------------------


def test_the_grouped_products_tile_at_width_512_of_2048_divides():
    """``megablox`` asks for ``(rows, 2048, 512)`` forward and for its two
    transposes in the backward pass, over 32 groups in a buffer of 65,536
    rows: whole tiles of lanes that divide the contraction and the columns."""
    assert (2048, 512) in GMM_TILES and (512, 2048) in GMM_TILES and 512 not in GMM_TILES
    for k, n in ((2048, 512), (512, 2048)):
        rows, contraction, columns = gmm_tiling(65536, k, n)
        assert k % contraction == 0 and (contraction % 128 == 0 or contraction == k), (k, n)
        assert columns % 128 == 0 and n % columns == 0, (k, n, columns)
        assert rows % 8 == 0 and 65536 % rows == 0


# -- the scopes ---------------------------------------------------------------


def test_every_part_is_named_in_both_passes_and_the_gate_stands_between_core_and_output():
    cfg = laguna_test_config()
    model = LagunaModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embedding", "final_norm", "lm_head", "layer_0", "layer_1", "layer_2"}
    assert set(params["layer_1"]["attn"]) == {"q_proj", "k_proj", "v_proj", "gate_proj", "out_proj"}
    assert set(params["layer_1"]["moe"]) == {"router", "correction_bias", "experts_gate",
                                             "experts_up", "experts_down", "shared"}
    text = jax.jit(jax.grad(laguna_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    forward = [parse_model_part(str(eqn.source_info.name_stack)) for eqn in jax.make_jaxpr(
        laguna_loss_fn(model))(params, ids).eqns]
    order = [p for p, before in zip(forward, [None] + forward) if p and p != before]
    attention = ["attn_proj", "{core}", "attn_gate", "attn_proj"]
    experts = ["moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"]
    assert order == (["embed"] + [p.format(core="attn_core") for p in attention] + ["dense_mlp"]
                     + [p.format(core="attn_window_core") for p in attention] + experts
                     + [p.format(core="attn_core") for p in attention] + experts + ["head"])
