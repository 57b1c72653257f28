"""Nemotron-3-Super (``nemotron_h``) at toy sizes on the CPU: the program's model
against the benchmark's plain reference on seeded weights, a pattern that holds
every kind of block and each kind alone; the mixer's convolution against a
direct sum; **the shares add up to the model**: the mixer's output over the head
shares, attention's over its head shares, the expert layer's over the expert
shares with what every chip computes alike counted once; the engine on four
devices; and the scopes that name the model's parts.  The shared parts
(``RMSNorm``, ``shift``, the next-token loss) have their tests in
``test_decoder.py``, the attention kernel in ``test_causal_attention.py``, the
scan in ``test_ssd_scan.py``; every comparison here runs both sides compiled
(``helpers.compiled``)."""

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
from bagua_tpu.models.nemotron_h import (
    PUBLISHED_PATTERN,
    Attention,
    LatentExperts,
    Mamba2Mixer,
    NemotronHConfig,
    NemotronHModel,
    causal_conv_silu,
    nemotron_h_loss_fn,
    nemotron_h_test_config,
)
from bagua_tpu.observability.scope_grammar import format_model_label, parse_model_part

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402

PARTS = ("ssm_proj", "ssm_conv", "ssm_core", "attn_proj", "attn_core", "moe_route", "moe_latent",
         "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "dense_mlp", "head")
PATTERNS = {"every_kind": "ME*-", "mixer": "M", "experts": "E", "attention": "*", "mlp": "-",
            "period": "EMEM*"}


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/nemotron-3-super.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/nemotron_h.py")


def toy_sizes(adapter, pattern=None, seq_len=32, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: 2 of 4 mixer heads (one of two groups), 2 of 4 query heads on one of
    two key-value heads, 2 held of 16 experts with 5 chosen, chunks of 16."""
    config = manifest.load_json("benchmark", "configs", "nemotron-3-super.json")
    config = {**config, **config["toy"], **overrides}
    if pattern is not None:
        config.update(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern))
    return adapter.sizes(config, {"seq_len": seq_len})


def whole(sz):
    """The same sizes with nothing cut: every head, every expert."""
    return {**sz, "experts_held": (0, sz["routed_experts_total"]),
            "mamba_heads_held": (0, sz["mamba_heads_total"]),
            "attention_heads_held": (0, sz["attention_heads_total"])}


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(
        adapter, reference, seed, pattern):
    sz = toy_sizes(adapter, PATTERNS[pattern])
    assert sz["seq_len"] > sz["chunk_size"]  # the state crosses chunks
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = NemotronHModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(nemotron_h_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
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
    """Nothing cut: four heads in two groups, two key-value heads, all sixteen
    experts (a buffer of ``tokens x 5`` rows, every choice's own)."""
    sz = whole(toy_sizes(adapter))
    ref_params = reference.init_params(jax.random.PRNGKey(7), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(8), 2, sz)
    model = NemotronHModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(nemotron_h_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(adapter.to_program(ref_grads, sz, cast=False))):
        if "correction_bias" not in jax.tree_util.keystr(path):
            assert rel_err(g, w) < 2e-4, (jax.tree_util.keystr(path), rel_err(g, w))


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    model = NemotronHModel(adapter.model_config(sz))
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
    published = manifest.load_json("benchmark", "configs", "nemotron-3-super.json")
    keys = {**published, **published["published"], "num_nextn_predict_layers": 0}
    cfg = NemotronHConfig.from_hf(keys, experts_held=(8, 8), mamba_heads_held=(16, 16),
                                  attention_heads_held=(4, 4))
    # the defaults are the published model
    assert cfg == NemotronHConfig(experts_held=(8, 8), mamba_heads_held=(16, 16),
                                  attention_heads_held=(4, 4))
    assert cfg.hybrid_override_pattern == PUBLISHED_PATTERN and len(PUBLISHED_PATTERN) == 88
    assert [PUBLISHED_PATTERN.count(kind) for kind in "ME*-"] == [40, 40, 8, 0]
    assert PUBLISHED_PATTERN[26:37] == published["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_latent_size) == (512, 22, 1024)
    assert (cfg.mamba_num_heads, cfg.n_groups, cfg.ssm_state_size, cfg.chunk_size) == (128, 8, 128, 128)
    assert cfg.held == (8, 8) and cfg.mamba_held == (16, 16) and cfg.attention_held == (4, 4)
    assert cfg.key_value_heads_held == 1 and NemotronHConfig().key_value_heads_held == 2
    assert NemotronHConfig(attention_heads_held=(16, 16)).key_value_heads_held == 1
    with pytest.raises(ValueError, match="is no range"):
        NemotronHConfig(experts_held=(510, 8))
    with pytest.raises(ValueError, match="no whole number of groups"):
        NemotronHConfig(mamba_heads_held=(0, 8))
    with pytest.raises(ValueError, match="neither whole key-value heads"):
        NemotronHConfig(attention_heads_held=(0, 24))
    with pytest.raises(ValueError, match="is no one of"):
        NemotronHConfig(hybrid_override_pattern="MEX", num_hidden_layers=3)
    # multi-token prediction is left out of the program: the published 1 is refused, not ignored
    with pytest.raises(NotImplementedError, match="num_nextn_predict_layers"):
        NemotronHConfig.from_hf({**published, **published["published"]})
    with pytest.raises(NotImplementedError, match="n_group"):
        NemotronHConfig(n_group=2)
    assert nemotron_h_test_config().hybrid_override_pattern == "ME*-"


# -- the mixer's convolution --------------------------------------------------


def direct_conv_silu(xbc, taps, bias):
    """``silu(bias + sum_i taps[i] * xbc_{t - 3 + i})``, one position and tap
    at a time."""
    xbc, taps, bias = (np.asarray(v, np.float64) for v in (xbc, taps, bias))
    out = np.zeros(xbc.shape) + bias
    last = taps.shape[0] - 1
    for pos in range(xbc.shape[1]):
        for i in range(last + 1):
            if pos - last + i >= 0:  # zeros before the start
                out[:, pos] += taps[i] * xbc[:, pos - last + i]
    return out / (1.0 + np.exp(-out))


def test_the_convolution_equals_a_direct_sum_and_its_backward_pass_autodiffs():
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    xbc = jax.random.normal(keys[0], (2, 9, 5), jnp.float32)
    taps = jax.random.normal(keys[1], (4, 5), jnp.float32)
    bias = jax.random.normal(keys[2], (5,), jnp.float32)
    probe = jax.random.normal(keys[3], (2, 9, 5), jnp.float32)
    got = compiled(causal_conv_silu, xbc, taps, bias)
    assert got.dtype == xbc.dtype and rel_err(got, direct_conv_silu(xbc, taps, bias)) < 1e-6
    # causal, the last tap on the current position: position 0 sees it alone
    first = np.asarray(xbc[:, 0] * taps[3] + bias)
    np.testing.assert_allclose(got[:, 0], first / (1 + np.exp(-first)), rtol=1e-5)
    later = xbc.at[:, 5:].set(7.0)
    np.testing.assert_array_equal(compiled(causal_conv_silu, later, taps, bias)[:, :5], got[:, :5])

    def plain(xbc, taps, bias):
        shifted = [jnp.pad(xbc, ((0, 0), (3 - i, 0), (0, 0)))[:, :xbc.shape[1]] for i in range(4)]
        return jax.nn.silu(sum(taps[i] * shifted[i] for i in range(4)) + bias)

    got_g = compiled(jax.grad(
        lambda *a: jnp.sum(probe * causal_conv_silu(*a)), argnums=(0, 1, 2)), xbc, taps, bias)
    want_g = compiled(jax.grad(
        lambda *a: jnp.sum(probe * plain(*a)), argnums=(0, 1, 2)), xbc, taps, bias)
    for g, w in zip(got_g, want_g):
        assert rel_err(g, w) < 1e-5
    # bf16: computed in float32, rounded once; the input alone is kept
    low = xbc.astype(jnp.bfloat16)
    exact = direct_conv_silu(low.astype(jnp.float32), taps, bias)
    np.testing.assert_array_equal(compiled(causal_conv_silu, low, taps, bias),
                                  jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16))
    _, residuals = jax.vjp(causal_conv_silu, low, taps, bias)
    kept = sorted((x.shape, str(x.dtype)) for x in jax.tree.leaves(residuals))
    assert kept == sorted([((2, 9, 5), "bfloat16"), ((4, 5), "float32"), ((5,), "float32")])


# -- the shares add up to the model -------------------------------------------


def _mixer_share(w, sz, share):
    """Head share ``share``'s slices of an uncut mixer's weights, in the
    reference's layout: whole groups."""
    heads, groups = sz["mamba_heads_total"], sz["n_groups_total"]
    size, state = sz["mamba_head_dim"], sz["ssm_state_size"]
    held = sz["mamba_heads_held"][1]
    g_held = held * groups // heads
    inner = heads * size
    cols = lambda start, width, n: np.arange(start + n * width, start + (n + 1) * width)  # noqa: E731
    xbc = np.concatenate([cols(0, held * size, share), cols(inner, g_held * state, share),
                          cols(inner + groups * state, g_held * state, share)])
    w_in = np.concatenate([cols(0, held * size, share), inner + xbc,
                           cols(2 * inner + 2 * groups * state, held, share)])
    per_head, per_column = cols(0, held, share), cols(0, held * size, share)
    return {"norm": w["norm"], "w_in": w["w_in"][:, w_in], "conv_w": w["conv_w"][:, xbc],
            "conv_b": w["conv_b"][xbc], "dt_bias": w["dt_bias"][per_head],
            "a_log": w["a_log"][per_head], "d_skip": w["d_skip"][per_head],
            "gate_norm": w["gate_norm"][per_column], "w_out": w["w_out"][per_column]}


def test_the_mixers_head_shares_add_up_to_the_uncut_references_mixer(adapter, reference):
    """Two chips share the toy's mixer, a ``B``/``C`` group of two heads each
    (the cell: 16 of 128 heads, one of eight groups): the group norm runs over
    a group's columns, so each share computes its own, and the shares' results
    add up through ``W_out``."""
    sz = toy_sizes(adapter, "M")
    everything = whole(sz)
    w = reference.init_params(jax.random.PRNGKey(5), everything)["layers"][0]
    w = {**w, "w_out": 30.0 * w["w_out"]}
    a = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    shares = sz["mamba_heads_total"] // sz["mamba_heads_held"][1]
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda a, w: reference.mixer(a, w, everything), a, w)
        total = jnp.zeros_like(a)
        for share in range(shares):
            mine = {**sz, "mamba_heads_held": (share * sz["mamba_heads_held"][1],
                                              sz["mamba_heads_held"][1])}
            cut = _mixer_share(w, sz, share)
            cfg = adapter.model_config(mine, compute_dtype=jnp.float32)
            out = compiled(lambda params, a: Mamba2Mixer(cfg).apply({"params": params}, a),
                           adapter._block(cut)["mixer"], a)
            assert rel_err(out, compiled(
                lambda a, cut: reference.mixer(a, cut, mine), a, cut)) < 1e-5
            total = total + out
    assert shares == 2 and rel_err(total, want) < 1e-5
    assert rel_err(out, want) > 0.3  # no share alone is the mixer


@pytest.mark.parametrize("held", [1, 2], ids=["half_a_key_value_head", "a_key_value_head"])
def test_attentions_head_shares_add_up_to_the_uncut_references_attention(adapter, reference, held):
    """The cell's share is four query heads on one key-value head that serves
    sixteen: here one query head of a key-value head's two, and both."""
    sz = toy_sizes(adapter, "*")
    everything = whole(sz)
    heads, kv_heads, size = (sz["attention_heads_total"], sz["key_value_heads_total"],
                             sz["head_dim"])
    group = heads // kv_heads
    w = reference.init_params(jax.random.PRNGKey(5), everything)["layers"][0]
    w = {**w, "w_o": 30.0 * w["w_o"]}
    a = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda a, w: reference.attention(a, w, everything), a, w)
        total = jnp.zeros_like(a)
        for share in range(heads // held):
            first = share * held
            q_cols = np.arange(first * size, (first + held) * size)
            kv_first, kv_count = first // group, max(1, held // group)
            kv_cols = np.arange(kv_first * size, (kv_first + kv_count) * size)
            cut = {"norm": w["norm"], "w_q": w["w_q"][:, q_cols], "w_k": w["w_k"][:, kv_cols],
                   "w_v": w["w_v"][:, kv_cols], "w_o": w["w_o"][q_cols]}
            mine = {**sz, "attention_heads_held": (first, held)}
            cfg = adapter.model_config(mine, compute_dtype=jnp.float32)
            out = compiled(lambda params, a: Attention(cfg).apply({"params": params}, a),
                           adapter._block(cut)["attn"], a)
            assert rel_err(out, compiled(
                lambda a, cut: reference.attention(a, cut, mine), a, cut)) < 1e-5
            total = total + out
    assert rel_err(total, want) < 1e-5 and rel_err(out, want) > 0.3


def test_the_expert_shares_add_up_with_what_every_chip_computes_alike_counted_once(
        adapter, reference):
    """Eight chips share the toy's sixteen experts, two each (the cell: 8 of
    512 on each of 64): the router, both latent projections and the shared
    expert are whole on every chip; the routed parts add up *in the latent
    width*, ``W_lat_out`` is linear, and the shared expert counts once."""
    sz = toy_sizes(adapter, "E")
    everything = whole(sz)
    total_experts, held = sz["routed_experts_total"], sz["experts_held"][1]
    w = reference.init_params(jax.random.PRNGKey(5), everything)["layers"][0]
    w = {**w, "w_lat_out": 30.0 * w["w_lat_out"], "s_down": 30.0 * w["s_down"]}
    assert sz["init_std"] == 0.125  # the toy's matrices: the routed part is no rounding error
    a = jax.random.normal(jax.random.PRNGKey(6), (2, 32, sz["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = compiled(lambda a, w: reference.experts(a, w, everything), a, w)
        shared = compiled(lambda a, w: reference.relu2(a @ w["s_up"]) @ w["s_down"], a, w)
        routed = jnp.zeros_like(a)
        for share in range(total_experts // held):
            mine = {**sz, "experts_held": (share * held, held)}
            cut = {**w, "e_up": w["e_up"][share * held:(share + 1) * held],
                   "e_down": w["e_down"][share * held:(share + 1) * held]}
            cfg = adapter.model_config(mine, compute_dtype=jnp.float32)
            out = compiled(lambda params, a: LatentExperts(cfg).apply({"params": params}, a),
                           adapter._block(cut)["moe"], a)
            assert rel_err(out, compiled(
                lambda a, cut: reference.experts(a, cut, mine), a, cut)) < 1e-5
            routed = routed + (out - shared)
    assert total_experts // held == 8 and rel_err(shared + routed, want) < 1e-5
    # no share alone is the routed part, and the routed part is no small term beside the shared
    assert rel_err(out - shared, want - shared) > 0.3
    assert float(jnp.linalg.norm(want - shared)) > 0.1 * float(jnp.linalg.norm(shared))
    # every token made 5 choices of 16 and each share held 2: a buffer of two rows a token
    chosen, _ = compiled(lambda a, w: reference.route(a, w, everything), a, w)
    assert chosen.shape[-1] == 5 > held


def test_the_router_reads_the_hidden_state_and_the_gate_comes_before_the_norm(adapter, reference):
    """Two of the file's ``assumed`` lines, held against the program: a router
    on the latent, or a norm before the gate, is another function."""
    sz = toy_sizes(adapter, "ME")
    params = reference.init_params(jax.random.PRNGKey(0), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(1), 2, sz)
    model = NemotronHModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    def reference_loss(params):
        return float(compiled(lambda p, ids: reference.loss(p, ids, sz), params, ids))

    base = float(compiled(nemotron_h_loss_fn(model), adapter.to_program(params, sz), ids))
    assert base == pytest.approx(reference_loss(params), abs=2e-6)
    # the gate's z columns scaled: a norm *after* the gate undoes a common scale of y * silu(z)
    # only in part, a norm *before* it not at all; the loss moves either way, and with the
    # reference's
    moved = jax.tree.map(lambda x: x, params)
    inner = sz["mamba_heads_held"][1] * sz["mamba_head_dim"]
    moved["layers"][0]["w_in"] = moved["layers"][0]["w_in"].at[:, :inner].multiply(3.0)
    moved["layers"][0]["w_out"] = 50.0 * moved["layers"][0]["w_out"]
    got = float(compiled(nemotron_h_loss_fn(model), adapter.to_program(moved, sz), ids))
    assert got == pytest.approx(reference_loss(moved), abs=2e-5)
    assert abs(got - base) > 1e-4


# -- the engine on four devices -----------------------------------------------


def test_four_devices_through_train_step_give_the_references_gradient_of_the_global_batch(
        adapter, reference):
    sz = toy_sizes(adapter)
    lr = 0.5
    group = bagua_tpu.init_process_group(devices=jax.devices()[:4])
    ref_params = reference.init_params(jax.random.PRNGKey(21), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(22), 4, sz)  # a sequence a device
    model = NemotronHModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    ddp = DistributedDataParallel(
        nemotron_h_loss_fn(model), optax.sgd(lr), GradientAllReduceAlgorithm(),
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


def test_every_part_is_named_in_both_passes_and_a_block_is_one_part_alone():
    cfg = nemotron_h_test_config()
    model = NemotronHModel(cfg)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embedding", "final_norm", "lm_head"} | {f"layer_{n}" for n in range(4)}
    assert [sorted(params[f"layer_{n}"]) for n in range(4)] == [
        ["mixer", "norm"], ["moe", "norm"], ["attn", "norm"], ["mlp", "norm"]]
    assert set(params["layer_0"]["mixer"]) == {"in_proj", "conv_taps", "conv_bias", "dt_bias",
                                               "A_log", "D", "norm_scale", "out_proj"}
    assert set(params["layer_1"]["moe"]) == {"router", "correction_bias", "latent_in", "latent_out",
                                             "experts_up", "experts_down", "shared"}
    # the family's initial state-space parameters: decays of every length, time steps in range
    mixer = params["layer_0"]["mixer"]
    assert np.all((np.exp(mixer["A_log"]) >= 1) & (np.exp(mixer["A_log"]) <= 16))
    steps = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert np.all((steps >= 0.00099) & (steps <= 0.101)) and np.all(np.asarray(mixer["D"]) == 1)
    text = jax.jit(jax.grad(nemotron_h_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    for absent in ("conv_core", "attn_window_core", "exit_gate"):
        assert format_model_label(absent) not in text
    forward = [parse_model_part(str(eqn.source_info.name_stack)) for eqn in jax.make_jaxpr(
        nemotron_h_loss_fn(model))(params, ids).eqns]
    order = [p for p, before in zip(forward[1:], forward) if p and p != before]
    assert order == ["ssm_proj", "ssm_conv", "ssm_core", "ssm_proj",
                     "moe_route", "moe_latent", "moe_dispatch", "moe_experts", "moe_combine",
                     "moe_latent", "moe_shared",
                     "attn_proj", "attn_core", "attn_proj", "dense_mlp", "head"]
