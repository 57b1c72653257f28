"""GLM-4.7-Flash at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights, one chip's share against the
whole layer, the dropless dispatch under a forced imbalance, the latent
attention's column order against the stored one, and the scopes that name the
model's parts in a device trace.  The attention kernel's own tests are in
``test_causal_attention.py``, the shared parts' in ``test_decoder.py``; every
comparison here runs both sides compiled (``helpers.compiled``)."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.glm_moe import (
    GlmMoeConfig,
    GlmMoeModel,
    SparseExperts,
    glm_moe_loss_fn,
    glm_moe_test_config,
    latent_qk,
    pairs_apart,
)
from bagua_tpu.models.llama import apply_rope
from bagua_tpu.observability import trace_analysis as ta
from bagua_tpu.observability.annotations import model_scope
from bagua_tpu.observability.scope_grammar import format_model_label, parse_model_part
from bagua_tpu.parallel.moe.dropless import collect, dropless_experts, sigmoid_topk_route, spread

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "ci"))
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402
from trim_capture import xspace_bytes  # noqa: E402

PARTS = ("attn_proj", "attn_core", "moe_route", "moe_dispatch", "moe_experts",
         "moe_combine", "moe_shared", "dense_mlp", "head")


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/glm-4.7-flash.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/glm_moe.py")


def toy_sizes(adapter, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: 2 held of 8 experts, top-2, one dense and two expert layers."""
    config = manifest.load_json("benchmark", "configs", "glm-4.7-flash.json")
    config = {**config, **config["toy"], **overrides}
    return adapter.sizes(config, {"seq_len": 32})


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("nextn", [0, 1], ids=["no_prediction_module", "prediction_module"])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(adapter, reference, nextn):
    sz = toy_sizes(adapter, num_nextn_predict_layers=nextn)
    ref_params = reference.init_params(jax.random.PRNGKey(3), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(4), 2, sz)
    model = GlmMoeModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(glm_moe_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "correction_bias" in name:  # steers the choice only
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), name
        else:
            assert np.linalg.norm(w) > 0, name
            assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    assert ("mtp_block" in grads) == bool(nextn)


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter, num_nextn_predict_layers=1)
    model = GlmMoeModel(adapter.model_config(sz))
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
    published = manifest.load_json("benchmark", "configs", "glm-4.7-flash.json")
    cfg = GlmMoeConfig.from_hf({**published, **published["published"]}, experts_held=(8, 8))
    assert (cfg.n_routed_experts, cfg.num_hidden_layers, cfg.vocab_size) == (64, 47, 154880)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.v_head_dim) == (768, 512, 256)
    assert cfg.held == (8, 8) and GlmMoeConfig().held == (0, 64)
    with pytest.raises(ValueError, match="is no range"):
        GlmMoeConfig(experts_held=(60, 8))


# -- one chip's share and the whole layer -------------------------------------


def expert_layer_weights(reference, sz, key):
    whole = {**sz, "experts_held": (0, sz["routed_experts_total"]), "num_hidden_layers": 2,
             "num_nextn_predict_layers": 0}
    return whole, reference.init_params(key, whole)["layers"][1]


def test_the_eight_shares_add_up_to_the_uncut_references_layer(adapter, reference):
    sz = toy_sizes(adapter)
    total = sz["routed_experts_total"]
    whole, w = expert_layer_weights(reference, sz, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, sz["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda h, w: reference.expert_mlp(h, w, whole), h, w)
        shared = compiled(lambda h, w: reference.swiglu(h, w["s_gate"], w["s_up"], w["s_down"]), h, w)
        routed = jnp.zeros_like(h)
        for share in range(total):  # each of the eight shares holds one expert
            held = (share, 1)
            cfg = adapter.model_config({**sz, "experts_held": held}, compute_dtype=jnp.float32)
            mine = {k: v[share:share + 1] for k, v in w.items() if k.startswith("e_")}
            params = adapter._block({**w, **mine, "attn_norm": 0, "w_dq": 0, "q_norm": 0, "w_uq": 0,
                                     "w_dkv": 0, "kv_norm": 0, "w_ukv": 0, "w_o": 0,
                                     "mlp_norm": 0})["moe"]
            out = compiled(lambda params, h: SparseExperts(cfg).apply({"params": params}, h),
                           params, h)
            # what every chip computes alike, the shared expert, counted once
            routed = routed + (out - shared)
    assert total == 8 and rel_err(routed + shared, want) < 1e-5
    # and no share alone is the layer
    assert rel_err(out, want) > 0.05


def test_routing_drops_nothing_when_every_token_goes_to_one_held_expert():
    tokens, hidden, width, experts = 96, 16, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.float32)
    gate, up = (0.3 * jax.random.normal(k, (2, hidden, width)) for k in keys[1:3])
    down = 0.3 * jax.random.normal(keys[3], (2, width, hidden))
    # a router that says nothing and a bias that sends every token to experts 1 and 6
    bias = jnp.zeros(experts).at[jnp.array([1, 6])].set(1.0)
    chosen, weights = compiled(
        lambda x, bias: sigmoid_topk_route(x, jnp.zeros((hidden, experts)), bias, 2, 1.8), x, bias)
    assert set(np.unique(chosen)) == {1, 6}
    np.testing.assert_allclose(weights, 0.9, rtol=1e-6)  # 0.5 / (0.5 + 0.5) * 1.8
    got = compiled(lambda *a: dropless_experts(*a, held=(0, 2), num_experts=experts),
                   x, chosen, weights, gate, up, down)
    # expert 1 is the second of the two held: all 96 rows are its, none is lost
    want = compiled(lambda x, gate, up, down: 0.9 * (
        jax.nn.silu(x @ gate[1]) * (x @ up[1])) @ down[1], x, gate, up, down)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert np.all(np.linalg.norm(np.asarray(got), axis=-1) > 0)
    # a share that holds neither expert adds nothing, and its gradient is finite
    none, grad = compiled(jax.value_and_grad(lambda x: jnp.sum(dropless_experts(
        x, chosen, weights, gate, up, down, held=(2, 2), num_experts=experts))), x)
    assert float(none) == 0.0 and np.all(np.isfinite(np.asarray(grad)))


def test_dispatch_gradients_match_a_dense_evaluation():
    tokens, hidden, width, experts, k = 40, 12, 6, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(8), 6)
    x = jax.random.normal(keys[0], (tokens, hidden))
    router = jax.random.normal(keys[1], (hidden, experts))
    bias = 0.1 * jax.random.normal(keys[2], (experts,))
    gate, up = (0.3 * jax.random.normal(kk, (3, hidden, width)) for kk in keys[3:5])
    down = 0.3 * jax.random.normal(keys[5], (3, width, hidden))
    held = (4, 3)

    def sparse(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, k, 1.8)
        return jnp.sum(jnp.sin(dropless_experts(
            x, chosen, weights, gate, up, down, held=held, num_experts=experts)))

    def dense(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, k, 1.8)
        out = 0.0
        for n in range(held[1]):
            w = jnp.sum(jnp.where(chosen == held[0] + n, weights, 0.0), axis=-1, keepdims=True)
            out = out + w * ((jax.nn.silu(x @ gate[n]) * (x @ up[n])) @ down[n])
        return jnp.sum(jnp.sin(out))

    args = (x, router, gate, up, down)
    with jax.default_matmul_precision("highest"):
        got = compiled(jax.value_and_grad(sparse, argnums=range(5)), *args)
        want = compiled(jax.value_and_grad(dense, argnums=range(5)), *args)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for g, w in zip(got[1], want[1]):
        assert rel_err(g, w) < 1e-5


def _row_order(seed, tokens, k, experts, count):
    """``(perm, inverse, n_live)`` as the layer builds it, from random choices."""
    key = jax.random.randint(jax.random.PRNGKey(seed), (tokens * k,), 0, experts)
    perm = jnp.argsort(key, stable=True)
    return perm, jnp.argsort(perm), jnp.sum(key < count)


@pytest.mark.parametrize("scaled", [True, False], ids=["scaled", "unscaled"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_spread_and_collect_are_each_others_transpose(dtype, scaled):
    tokens, k, hidden = 24, 3, 16
    order = perm, inverse, n_live = _row_order(11, tokens, k, experts=8, count=3)
    assert 0 < int(n_live) < tokens * k
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    src = jax.random.normal(keys[0], (tokens, hidden)).astype(dtype)
    buffer = jax.random.normal(keys[1], (tokens * k, hidden)).astype(dtype)
    scale = jax.random.uniform(keys[2], (tokens * k,), minval=0.5) if scaled else None
    weight = scale[inverse].reshape(tokens, k) if scaled else None
    live = (jnp.arange(tokens * k) < n_live)[:, None]
    def spread_(src, scale):
        return compiled(lambda src, scale: spread(src, scale, order, k), src, scale)

    def collect_(buffer, weight):
        return compiled(lambda buffer, weight: collect(buffer, weight, order, k), buffer, weight)

    # the passes against their definitions, written plainly
    plain_scale = scale[:, None] if scaled else 1.0
    want_rows = compiled(lambda src: jnp.where(
        live, (plain_scale * src[perm // k]).astype(dtype), 0), src)
    np.testing.assert_array_equal(spread_(src, scale), want_rows)
    want_tokens = compiled(lambda buffer: jnp.sum((jnp.where(live, buffer, 0).astype(
        jnp.float32) * plain_scale)[inverse].reshape(tokens, k, hidden), axis=1).astype(dtype), buffer)
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(collect_(buffer, weight), np.float32),
                               np.asarray(want_tokens, np.float32), **tol)
    # a dead row of the buffer is whatever it held: NaN here, and never read
    poisoned = jnp.where(live, buffer, jnp.nan)
    np.testing.assert_array_equal(collect_(poisoned, weight), collect_(buffer, weight))
    # the transpose of each is the other, to the last bit
    got = compiled(lambda src, buffer: jax.vjp(
        lambda s: spread(s, scale, order, k), src)[1](buffer)[0], src, buffer)
    np.testing.assert_array_equal(got, collect_(buffer, weight))
    got = compiled(lambda buffer, src: jax.vjp(
        lambda b: collect(b, weight, order, k), buffer)[1](src)[0], buffer, src)
    np.testing.assert_array_equal(got, spread_(src, scale))
    # and what autodiff makes of the plain definitions (a scatter-add)
    plain = compiled(lambda src, buffer: jax.vjp(
        lambda s: jnp.where(live, plain_scale * s.astype(jnp.float32)[perm // k], 0),
        src)[1](buffer.astype(jnp.float32))[0], src, buffer)
    np.testing.assert_allclose(
        np.asarray(collect_(buffer, weight), np.float32), plain, **tol)
    if scaled:  # the row-wise dot products: the gradients of the scale and of the weight
        d_scale = compiled(lambda scale, buffer: jax.vjp(
            lambda c: spread(src, c, order, k), scale)[1](buffer)[0], scale, buffer)
        want = jnp.where(live[:, 0], jnp.sum(
            buffer.astype(jnp.float32) * src.astype(jnp.float32)[perm // k], axis=-1), 0)
        np.testing.assert_allclose(d_scale, want, **tol)
        d_weight = compiled(lambda weight, src: jax.vjp(
            lambda w: collect(poisoned, w, order, k), weight)[1](src)[0], weight, src)
        np.testing.assert_allclose(d_weight, want[inverse].reshape(tokens, k), **tol)


LOADS = {
    # experts, held, bias that steers the choice
    "an_eighth_held": (16, (6, 2), None),
    "a_quarter_held": (8, (4, 2), None),
    "every_expert_held": (4, (0, 4), None),
    "every_token_on_one_held_expert": (8, (5, 2), (6, 1)),
}


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_layer_and_every_gradient_match_a_dense_evaluation_at_every_load(dtype, load):
    experts, held, favoured = LOADS[load]
    tokens, hidden, width, k = 48, 16, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(21), 7)
    x = jax.random.normal(keys[0], (tokens, hidden)).astype(dtype)
    router = 0.5 * jax.random.normal(keys[1], (hidden, experts))
    bias = 0.1 * jax.random.normal(keys[2], (experts,))
    if favoured:  # the k experts every token goes to: one of them is held
        bias = bias.at[jnp.array(favoured)].add(8.0)
    gate, up = (0.3 * jax.random.normal(kk, (held[1], hidden, width)) for kk in keys[3:5])
    down = 0.3 * jax.random.normal(keys[5], (held[1], width, hidden))
    probe = jax.random.normal(keys[6], (tokens, hidden))

    def sparse(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, k, 1.8)
        out = dropless_experts(x, chosen, weights, gate, up, down, held=held, num_experts=experts)
        return jnp.sum(probe * out.astype(jnp.float32)), (out, chosen)

    def dense(x, router, gate, up, down):
        chosen, weights = sigmoid_topk_route(x, router, bias, k, 1.8)
        x = x.astype(jnp.float32)
        out = 0.0
        for n in range(held[1]):
            w = jnp.sum(jnp.where(chosen == held[0] + n, weights, 0.0), axis=-1, keepdims=True)
            out = out + w * ((jax.nn.silu(x @ gate[n]) * (x @ up[n])) @ down[n])
        return jnp.sum(probe * out), (out, chosen)

    args = (x, router, gate, up, down)
    with jax.default_matmul_precision("highest"):
        (_, (got_out, chosen)), got = compiled(
            jax.value_and_grad(sparse, argnums=range(5), has_aux=True), *args)
        (_, (want_out, _)), want = compiled(
            jax.value_and_grad(dense, argnums=range(5), has_aux=True), *args)
    mine = (chosen >= held[0]) & (chosen < held[0] + held[1])
    if favoured:
        assert set(np.unique(chosen)) == set(favoured) and np.all(np.sum(mine, axis=-1) == 1)
    elif held[1] == experts:
        assert np.all(mine)
    else:
        assert 0 < int(jnp.sum(mine)) < tokens * k
    bound = 1e-5 if dtype == jnp.float32 else 2e-2
    assert rel_err(got_out, want_out) < bound
    for name, g, w in zip(("x", "router", "gate", "up", "down"), got, want):
        assert g.dtype == w.dtype and rel_err(g, w) < bound, (name, rel_err(g, w))


# -- attention ----------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [1, 3], ids=["one_head", "three_heads"])
@pytest.mark.parametrize("nope,rope", [(12, 4), (192, 64)], ids=["rope4", "rope64"])
def test_scores_from_columns_set_apart_equal_scores_from_the_stored_interleaved_columns(
        nope, rope, heads, dtype):
    """The layer puts the two columns of each rotary pair half a head apart,
    in ``q`` and in ``k`` alike, by reordering *weights*; the scores ``q k^T``
    (the scale is in ``q``) and their gradients on the *stored* columns of
    ``q_up`` and ``kv_down`` are those of ``apply_rope`` on the stored,
    interleaved columns.  Both head sizes are powers of four, so the scale is
    a power of two and bf16 rounds alike on both sides."""
    b, t, hidden, q_rank, rank, theta = 2, 16, 20, 24, 12, 1e4
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    x, c_q, c_kv = (jax.random.normal(kk, (b, t, width), dtype)
                    for kk, width in zip(keys, (hidden, q_rank, rank)))
    q_up = 0.3 * jax.random.normal(keys[3], (q_rank, heads, nope + rope))
    k_up = 0.3 * jax.random.normal(keys[4], (rank, heads, nope))
    rope_down = 0.3 * jax.random.normal(keys[5], (hidden, rope))  # kv_down's rotary columns
    readout = jax.random.normal(keys[6], (b, heads, t, t))
    positions = jnp.arange(t)

    def product(pattern, a, w):
        return jnp.einsum(pattern, a, w.astype(dtype), preferred_element_type=jnp.float32).astype(dtype)

    def set_apart(q_up, rope_down):
        k_rope = product("bth,hr->btr", x, jnp.concatenate(pairs_apart(rope_down), axis=1))
        q, k = latent_qk(c_q, c_kv, k_rope, q_up, k_up, rope, theta, dtype)
        assert q.dtype == k.dtype == dtype and q.shape == k.shape == (b, heads, t, nope + rope)
        return jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)

    def stored(q_up, rope_down):
        q = product("btr,rhd->bthd", c_q, q_up)
        q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], positions, theta)], axis=-1)
        k_rope = apply_rope(product("bth,hr->btr", x, rope_down)[:, :, None], positions, theta)
        k = jnp.concatenate([product("btr,rhd->bthd", c_kv, k_up),
                             jnp.broadcast_to(k_rope, (b, t, heads, rope))], axis=-1)
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32) / math.sqrt(nope + rope)

    def read(scores):
        def readout_and_scores(*weights):
            s = scores(*weights)
            return jnp.sum(readout * s), s
        return compiled(jax.value_and_grad(readout_and_scores, argnums=(0, 1), has_aux=True),
                        q_up, rope_down)

    with jax.default_matmul_precision("highest"):
        (got, got_scores), got_grads = read(set_apart)
        (want, want_scores), want_grads = read(stored)
    near = 1e-5 if dtype == jnp.float32 else 2e-3  # bf16: a last bit of q or k by the order of a float32 sum
    assert rel_err(got_scores, want_scores) < near
    assert float(got) == pytest.approx(float(want), rel=near, abs=near)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape and np.linalg.norm(w) > 0
        assert rel_err(g, w) < near


# -- the scopes ---------------------------------------------------------------


def test_model_scope_grammar_round_trips_and_reaches_both_passes():
    assert format_model_label("attn_core") == "bagua_model/part=attn_core"
    fwd = "jit(step)/bagua_step/phase=fwd_bwd/jvp(GlmMoeModel)/layer_1/moe/bagua_model/part=moe_route/dot_general"
    bwd = ("jit(step)/bagua_step/phase=fwd_bwd/transpose(jvp(GlmMoeModel))/layer_1/attn/"
           "bagua_model/part=attn_core/pallas_call")
    assert parse_model_part(fwd) == "moe_route" and parse_model_part(bwd) == "attn_core"
    assert parse_model_part("jit(step)/bagua_step/phase=fwd_bwd/add") is None
    assert parse_model_part(None) is None
    # the innermost frame names the part
    assert parse_model_part("bagua_model/part=moe_shared/x/bagua_model/part=head/dot") == "head"

    cfg = glm_moe_test_config(num_nextn_predict_layers=0)
    model = GlmMoeModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    text = jax.jit(jax.grad(glm_moe_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    with model_scope("head"):
        pass


def test_summary_gives_model_part_ms_beside_the_partition(tmp_path):
    """Two steps of 100 µs: forward 10 under ``attn_core`` and 5 under no
    part, backward 20 under ``attn_core``, 8 under ``moe_experts`` and 2
    under no part, the update 5."""
    fwd, bwd = "bagua_step/phase=fwd_bwd", "bagua_step/phase=fwd_bwd/transpose(jvp(m))"
    ops, modules = [], []
    for base in (0, 100):
        def op(n, start, end, op_name):
            ops.append((f"%fusion.{n} = f32[4] fusion()", 1000 * (1000 + base + start),
                        1000 * (end - start), {"op_name": op_name}))

        op(1, 0, 10, fwd + "/layer_0/bagua_model/part=attn_core/dot")
        op(2, 10, 15, fwd + "/layer_0/add")
        op(3, 15, 35, bwd + "/layer_0/bagua_model/part=attn_core/dot")
        op(4, 35, 43, bwd + "/layer_1/bagua_model/part=moe_experts/gmm")
        op(5, 43, 45, bwd + "/layer_0/mul")
        op(6, 45, 50, "bagua_step/phase=optimizer")
        modules.append(("jit_local_step(1)", 1000 * (1000 + base), 1000 * 50, {}))
    path = str(tmp_path / "parts.xplane.pb")
    with open(path, "wb") as f:
        f.write(xspace_bytes([("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, ops)])]))
    got = ta.summarize_capture(path)
    ms = pytest.approx
    assert got["partition_ms"] == {"forward": ms(0.015), "backward": ms(0.030), "optimizer": ms(0.005)}
    assert got["model_part_ms"] == {"attn_core": ms(0.030), "moe_experts": ms(0.008), "other": ms(0.007)}
    assert sum(got["model_part_ms"].values()) == ms(
        got["partition_ms"]["forward"] + got["partition_ms"]["backward"])


def test_a_model_without_part_scopes_has_no_model_part_ms(tmp_path):
    ops = [("%fusion.1 = f32[4] fusion()", 1_000_000, 10_000, {"op_name": "bagua_step/phase=fwd_bwd/dot"})]
    modules = [("jit_local_step(1)", 1_000_000, 10_000, {})]
    path = str(tmp_path / "plain.xplane.pb")
    with open(path, "wb") as f:
        f.write(xspace_bytes([("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, ops)])]))
    assert "model_part_ms" not in ta.summarize_capture(path)
