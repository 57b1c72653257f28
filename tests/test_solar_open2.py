"""Solar-Open2-250B (``solar_open2``) at toy sizes on the CPU: the program's
model against the benchmark's plain reference on seeded weights (whose delta
rule is the recurrence itself, position by position), the stack with both kinds
of mixer and each kind alone; **the shares add up to the model**: the KDA
mixer's output over the head shares, the GQA mixer's over its head shares with
the gate's columns, the expert layer's over the expert shares with what every
chip computes alike counted once; ``beta`` in (0, 2) and the gate from the
normed input; the engine on four devices; and the scopes that name the model's
parts.  The shared parts (``RMSNorm``, the convolution, the attention layer and
its gate's two widths, the next-token loss) have their tests in
``test_decoder.py``, the delta rule in ``test_delta_rule.py``; every comparison
here runs both sides compiled (``helpers.compiled``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models.decoder import GroupedQueryAttention
from bagua_tpu.models.solar_open2 import (
    KdaMixer,
    LinearAttnConfig,
    SolarOpen2Config,
    SolarOpen2Model,
    SparseExperts,
    solar_open2_loss_fn,
    solar_open2_test_config,
)
from bagua_tpu.observability.scope_grammar import format_model_label, parse_model_part

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402

PARTS = ("kda_proj", "kda_conv", "kda_core", "kda_gate_norm", "attn_proj", "attn_gate",
         "attn_core", "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
         "head")
#: ``(layers, gqa_layers)``: the toy's period, and each kind of mixer alone
STACKS = {"period": (3, (0,)), "kda": (1, ()), "gqa": (1, (0,))}


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/solar-open2-250b.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/solar_open2.py")


def toy_sizes(adapter, stack="period", seq_len=32, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: 2 of 4 heads of each mixer (on 1 of 2 key-value heads), 4 held of 16
    experts with 3 chosen, chunks of 16."""
    config = manifest.load_json("benchmark", "configs", "solar-open2-250b.json")
    layers, gqa = STACKS[stack]
    config = {**config, **config["toy"], "num_hidden_layers": layers, "gqa_layers": list(gqa),
              **overrides}
    return adapter.sizes(config, {"seq_len": seq_len})


def whole(sz):
    """The same sizes with nothing cut: every head, every expert."""
    return {**sz, "experts_held": (0, sz["routed_experts_total"]),
            "heads_held": (0, sz["attention_heads_total"])}


def both_sides(adapter, reference, sz, ref_params, ids):
    model = SolarOpen2Model(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = compiled(jax.value_and_grad(solar_open2_loss_fn(model)),
                       adapter.to_program(ref_params, sz), ids)
        want = compiled(jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)),
                        ref_params, ids)
    return got, want


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("stack", sorted(STACKS))
@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(
        adapter, reference, seed, stack):
    sz = toy_sizes(adapter, stack)
    assert sz["seq_len"] > sz["chunk_size"]  # the state crosses chunks
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    (loss, grads), (ref_loss, ref_grads) = both_sides(adapter, reference, sz, ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "correction_bias" in name:  # steers the choice alone: no gradient on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), name
            continue
        assert np.linalg.norm(w) > 0, name
        assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    assert adapter.HEAD_LEAF == "['lm_head']" and grads["lm_head"].shape == (
        sz["hidden_size"], sz["vocab_size"])


def test_the_whole_model_matches_the_reference_too(adapter, reference):
    """Nothing cut: four heads on two key-value heads, all sixteen experts."""
    sz = whole(toy_sizes(adapter))
    ref_params = reference.init_params(jax.random.PRNGKey(7), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(8), 2, sz)
    (loss, grads), (ref_loss, ref_grads) = both_sides(adapter, reference, sz, ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(adapter.to_program(ref_grads, sz, cast=False))):
        if "correction_bias" not in jax.tree_util.keystr(path):
            assert rel_err(g, w) < 2e-4, (jax.tree_util.keystr(path), rel_err(g, w))


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    model = SolarOpen2Model(adapter.model_config(sz))
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
    published = manifest.load_json("benchmark", "configs", "solar-open2-250b.json")
    keys = {**published, **{k: v for k, v in published["published"].items() if "." not in k}}
    cfg = SolarOpen2Config.from_hf(keys, experts_held=(8, 8), heads_held=(8, 8))
    # the defaults are the published model
    assert cfg == SolarOpen2Config(experts_held=(8, 8), heads_held=(8, 8))
    assert cfg.gqa_layers == tuple(range(0, 48, 4)) and cfg.num_hidden_layers == 48
    assert cfg.linear_attn_config == LinearAttnConfig(4, 128, 64, None)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (320, 8, 1280)
    assert cfg.held == (8, 8) and cfg.held_heads == (8, 8) and cfg.key_value_heads_held == 1
    assert SolarOpen2Config().key_value_heads_held == 8 and cfg.chunk_size == 64
    assert SolarOpen2Config(heads_held=(4, 4)).key_value_heads_held == 1  # half a key-value head's
    with pytest.raises(ValueError, match="is no range"):
        SolarOpen2Config(experts_held=(316, 8))
    with pytest.raises(ValueError, match="neither whole key-value heads"):
        SolarOpen2Config(heads_held=(0, 12))
    with pytest.raises(ValueError, match="names a layer past"):
        SolarOpen2Config(num_hidden_layers=4)
    with pytest.raises(NotImplementedError, match="use_rope"):
        SolarOpen2Config(use_rope=True)
    with pytest.raises(NotImplementedError, match="kda_use_full_proj"):
        SolarOpen2Config(kda_use_full_proj=True)
    with pytest.raises(NotImplementedError, match="as many KDA heads"):
        SolarOpen2Config(linear_attn_config=LinearAttnConfig(4, 128, 32, None))
    assert solar_open2_test_config().gqa_layers == (0,)


# -- the shares add up to the model -------------------------------------------


def _columns(first, count, size):
    return np.arange(first * size, (first + count) * size)


def _kda_share(w, first, held, size):
    cols, heads = _columns(first, held, size), np.arange(first, first + held)
    cut = dict(w)
    for name in ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_f2", "w_g2"):
        cut[name] = w[name][:, cols]
    cut.update(dt_bias=w["dt_bias"][cols], b_g=w["b_g"][cols], a_log=w["a_log"][heads],
               w_b=w["w_b"][:, heads], w_o=w["w_o"][cols])
    return cut


def _gqa_share(w, first, held, size, group):
    cols = _columns(first, held, size)
    kv = _columns(first // group, max(1, held // group), size)
    return {**w, "w_q": w["w_q"][:, cols], "w_g": w["w_g"][:, cols], "w_o": w["w_o"][cols],
            "w_k": w["w_k"][:, kv], "w_v": w["w_v"][:, kv]}


@pytest.mark.parametrize("kind", ["kda", "gqa_half_a_key_value_head", "gqa_a_key_value_head"])
def test_a_mixers_head_shares_add_up_to_the_uncut_references_mixer(adapter, reference, kind):
    """The cell's share is 8 of 64 heads of each mixer: here 2 of the KDA
    mixer's 4 (``W_f1``, ``W_g1`` and the head norm's scale whole on every
    chip), and of the GQA mixer's 4 on 2 key-value heads one query head of a
    key-value head's two, or both, each with its columns of the gate."""
    stack, held = ("kda", 2) if kind == "kda" else ("gqa", 1 if "half" in kind else 2)
    sz = toy_sizes(adapter, stack)
    everything = whole(sz)
    heads = sz["attention_heads_total"]
    group = heads // sz["key_value_heads_total"]
    w = reference.init_params(jax.random.PRNGKey(5), everything)["layers"][0]
    w = {**w, "w_o": 30.0 * w["w_o"]}
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    ref_mixer = reference.kda if kind == "kda" else reference.attention
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda h, w: ref_mixer(h, w, everything), h, w)
        total = jnp.zeros_like(h)
        for first in range(0, heads, held):
            mine = {**sz, "heads_held": (first, held)}
            cfg = adapter.model_config(mine, compute_dtype=jnp.float32)
            if kind == "kda":
                cut = _kda_share(w, first, held, sz["kda_head_dim"])
                layer, params = KdaMixer(cfg), adapter._block(cut)["kda"]
            else:
                cut = _gqa_share(w, first, held, sz["head_dim"], group)
                layer = GroupedQueryAttention(held, cfg.key_value_heads_held, cfg.head_dim,
                                              jnp.float32, gate="column")
                params = adapter._block(cut)["attn"]
            out = compiled(lambda params, h: layer.apply({"params": params}, h), params, h)
            assert rel_err(out, compiled(lambda h, cut: ref_mixer(h, cut, mine), h, cut)) < 1e-5
            total = total + out
    assert rel_err(total, want) < 1e-5
    assert rel_err(out, want) > 0.3  # no share alone is the mixer


def test_the_expert_shares_add_up_with_what_every_chip_computes_alike_counted_once(
        adapter, reference):
    """Four chips share the toy's sixteen experts, four each (the cell: 8 of
    320 on each of 40): the router and the shared expert are whole on every
    chip, and the shared expert counts once."""
    sz = toy_sizes(adapter, "kda")
    everything = whole(sz)
    total_experts, held = sz["routed_experts_total"], sz["experts_held"][1]
    w = reference.init_params(jax.random.PRNGKey(5), everything)["layers"][0]
    w = {**w, "e_down": 30.0 * w["e_down"], "s_down": 30.0 * w["s_down"]}
    assert sz["init_std"] == 0.125  # the toy's matrices: the routed part is no rounding error
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda u, w: reference.experts(u, w, everything), u, w)
        shared = compiled(
            lambda u, w: reference.swiglu(u, w["s_gate"], w["s_up"], w["s_down"]), u, w)
        routed = jnp.zeros_like(u)
        for share in range(total_experts // held):
            mine = {**sz, "experts_held": (share * held, held)}
            cut = {**w, **{name: w[name][share * held:(share + 1) * held]
                           for name in ("e_gate", "e_up", "e_down")}}
            cfg = adapter.model_config(mine, compute_dtype=jnp.float32)
            out = compiled(lambda params, u: SparseExperts(cfg).apply({"params": params}, u),
                           adapter._block(cut)["moe"], u)
            assert rel_err(out, compiled(
                lambda u, cut: reference.experts(u, cut, mine), u, cut)) < 1e-5
            routed = routed + (out - shared)
    assert total_experts // held == 4 and rel_err(shared + routed, want) < 1e-5
    # no share alone is the routed part, and the routed part is no small term beside the shared
    assert rel_err(out - shared, want - shared) > 0.3
    assert float(jnp.linalg.norm(want - shared)) > 0.1 * float(jnp.linalg.norm(shared))


def test_beta_lies_in_0_2_and_the_gates_read_the_normed_input(adapter, reference):
    """Three of the file's ``assumed`` lines, held against the program: with
    ``beta``'s matrix scaled so that the sigmoid saturates both ways the loss
    is the doubled sigmoid's (and not the plain one's, which the reference
    computes with ``kda_allow_neg_eigval`` false); with the input norm's scale
    and both gates' matrices moved, the loss moves with the reference's."""
    sz = toy_sizes(adapter)
    params = reference.init_params(jax.random.PRNGKey(0), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(1), 2, sz)
    model = SolarOpen2Model(adapter.model_config(sz, compute_dtype=jnp.float32))

    def losses(params, sizes=sz):
        with jax.default_matmul_precision("highest"):
            return (float(compiled(solar_open2_loss_fn(model), adapter.to_program(params, sz), ids)),
                    float(compiled(lambda p, ids: reference.loss(p, ids, sizes), params, ids)))

    base, ref_base = losses(params)
    assert base == pytest.approx(ref_base, abs=2e-6)
    moved = jax.tree.map(lambda x: x, params)
    for n in (1, 2):
        moved["layers"][n]["w_b"] = 8.0 * moved["layers"][n]["w_b"]
        moved["layers"][n]["w_o"] = 30.0 * moved["layers"][n]["w_o"]
    h = reference.rms_norm(params["emb"][ids], moved["layers"][1]["norm_in"], sz["rms_norm_eps"])
    beta = 2.0 * jax.nn.sigmoid(h @ moved["layers"][1]["w_b"])
    assert float(beta.max()) > 1.9 and float(beta.min()) < 0.1  # both ends of (0, 2)
    got, want = losses(moved)
    assert got == pytest.approx(want, abs=2e-5)
    _, halved = losses(moved, {**sz, "kda_allow_neg_eigval": False})
    assert abs(got - halved) > 1e-3
    # the gates: from the layer's *normed* input, the norm's learned scale included
    gated = jax.tree.map(lambda x: x, params)
    for n, w in enumerate(gated["layers"]):
        w["norm_in"] = w["norm_in"] * jnp.linspace(0.5, 2.0, sz["hidden_size"])
        w["w_o"] = 30.0 * w["w_o"]
        for name in ("w_g", "w_g2"):
            if name in w:
                w[name] = 8.0 * w[name]
    got, want = losses(gated)
    assert got == pytest.approx(want, abs=2e-5) and abs(got - base) > 1e-3


# -- the engine on four devices -----------------------------------------------


def test_four_devices_through_train_step_give_the_references_gradient_of_the_global_batch(
        adapter, reference):
    sz = toy_sizes(adapter)
    lr = 0.5
    group = bagua_tpu.init_process_group(devices=jax.devices()[:4])
    ref_params = reference.init_params(jax.random.PRNGKey(21), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(22), 4, sz)  # a sequence a device
    model = SolarOpen2Model(adapter.model_config(sz, compute_dtype=jnp.float32))
    ddp = DistributedDataParallel(
        solar_open2_loss_fn(model), optax.sgd(lr), GradientAllReduceAlgorithm(),
        process_group=group, bucket_size_bytes=1 << 12)
    start = adapter.to_program(ref_params, sz)
    with jax.default_matmul_precision("highest"):
        state = ddp.init(start)
        assert ddp.plan.num_buckets > 4
        state, losses = ddp.train_step(state, ddp.shard_batch(ids))
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    assert float(np.mean(np.asarray(losses))) == pytest.approx(float(ref_loss), abs=2e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    flat = lambda tree: {jax.tree_util.keystr(p): x  # noqa: E731
                         for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    after, before, want = flat(state.params), flat(start), flat(want)
    for name, w in want.items():
        assert np.all(np.asarray(after[name]) == np.asarray(after[name][:1])), name  # the ranks agree
        if "correction_bias" in name:
            np.testing.assert_array_equal(after[name][0], before[name])
            continue
        # read back from the update, so to the rounding of a weight less lr x g: against the
        # leaf's own size where its gradient is small beside it
        grad = (np.asarray(before[name]) - np.asarray(after[name][0])) / lr
        spacing = 2.0 ** -23 * float(np.linalg.norm(before[name])) / lr
        assert np.linalg.norm(grad - w) < 2e-3 * np.linalg.norm(w) + 4 * spacing, name


# -- the scopes ---------------------------------------------------------------


def test_every_part_is_named_in_both_passes_and_the_parameters_are_the_layers_own():
    cfg = solar_open2_test_config(num_hidden_layers=2)  # a layer of each kind
    model = SolarOpen2Model(cfg)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = compiled(model.init, jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embedding", "final_norm", "lm_head", "layer_0", "layer_1"}
    assert [sorted(params[f"layer_{n}"]) for n in range(2)] == [
        ["attn", "input_norm", "moe", "post_mixer_norm"],
        ["input_norm", "kda", "moe", "post_mixer_norm"]]
    assert set(params["layer_0"]["attn"]) == {"q_proj", "k_proj", "v_proj", "gate_proj", "out_proj"}
    assert params["layer_0"]["attn"]["gate_proj"].shape == (32, 4 * 8)  # a value a head column
    kda = params["layer_1"]["kda"]
    assert set(kda) == {"q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "f_a_proj",
                        "f_b_proj", "dt_bias", "A_log", "b_proj", "g_a_proj", "g_b_proj", "g_bias",
                        "o_norm", "o_proj"}
    assert kda["f_a_proj"].shape == (32, 8) and kda["f_b_proj"].shape == (8, 32)  # through the head size
    assert kda["o_norm"].shape == (8,) and kda["A_log"].shape == (4,) and kda["dt_bias"].shape == (32,)
    # the KDA layer's initial decays: of every length, time steps in range
    assert np.all((np.exp(kda["A_log"]) >= 1) & (np.exp(kda["A_log"]) <= 16))
    steps = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert np.all((steps >= 0.00099) & (steps <= 0.101))
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    text = jax.jit(jax.grad(solar_open2_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    for absent in ("ssm_core", "attn_window_core", "dense_mlp"):
        assert format_model_label(absent) not in text
    forward = [parse_model_part(str(eqn.source_info.name_stack)) for eqn in jax.make_jaxpr(
        solar_open2_loss_fn(model))(params, ids).eqns]
    order = [p for p, before in zip(forward[1:], forward) if p and p != before]
    experts = ["moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"]
    kda_parts = ["kda_proj", "kda_conv", "kda_core", "kda_gate_norm", "kda_proj"]
    assert order == (["attn_proj", "attn_core", "attn_gate", "attn_proj"] + experts
                     + kda_parts + experts + ["head"])
