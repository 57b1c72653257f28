"""Ouro-2.6B at toy sizes on the CPU: the program's model against the
benchmark's plain reference on seeded weights at one, two and four passes; the
loop tied to a plain stack (``L`` layers run ``R`` times equal ``R x L`` layers
that hold copies, and a shared leaf's gradient is the sum of its copies'); the
exit distribution against a four-line oracle, and taken pass by pass; one pass
as the plain cross entropy; the exit that takes the head's gradient products in
the forward pass against autodiff of the plain composition, and the model with
it against a version that keeps everything and against the exit that rebuilds
its logits; the engine on four devices: one exchange a bucket of the summed
gradient, with the exchange inside the backward pass and after it; and the
scopes that name the model's parts and passes, with the summary's
``recompute`` class and ``model_pass_ms``.  The shared parts (the attention
layer, ``RMSNorm``, ``SwiGLU``) have their tests in ``test_decoder.py``; every
comparison here runs both sides compiled (``helpers.compiled``)."""

import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.analysis.verify import _abstract, collect_ir
from bagua_tpu.ddp import DistributedDataParallel
from bagua_tpu.models import ouro
from bagua_tpu.models.decoder import RMSNorm
from bagua_tpu.models.losses import softmax_cross_entropy
from bagua_tpu.models.ouro import (
    OuroBlock,
    OuroConfig,
    OuroModel,
    distribution_entropy,
    exit_distribution,
    exit_share,
    mean_weights,
    ouro_loss_fn,
    ouro_test_config,
)
from bagua_tpu.observability import trace_analysis as ta
from bagua_tpu.observability.annotations import pass_scope
from bagua_tpu.observability.scope_grammar import (
    format_model_label,
    format_pass_label,
    parse_model_part,
    parse_model_pass,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "ci"))
from benchmark import manifest  # noqa: E402
from helpers import compiled  # noqa: E402
from oracles import rel_err  # noqa: E402
from trim_capture import xspace_bytes  # noqa: E402

PARTS = ("embed", "attn_proj", "attn_core", "dense_mlp", "head", "exit_gate")
#: two programs of the same operations on the same operands, by compute dtype: rounding of another
#: order at most (a normed state's cotangents from its gate, its head and the later passes are
#: added in the compute dtype, in the order each program writes them)
BOUNDS = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


@pytest.fixture(scope="module")
def adapter():
    return manifest.load_module("benchmark/configs/ouro-2.6b.py")


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module("benchmark/reference/ouro.py")


def toy_sizes(adapter, **overrides):
    """The configuration's toy sizes through the adapter, as a dry run has
    them: two layers of four heads of 16, run four times."""
    config = manifest.load_json("benchmark", "configs", "ouro-2.6b.json")
    config = {**config, **config["toy"], **overrides}
    return adapter.sizes(config, {"seq_len": 32})


def leaves_by_name(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(tree)}


# -- the model against the plain reference ------------------------------------


@pytest.mark.parametrize("passes", [1, 2, 4])
@pytest.mark.parametrize("seed", [3, 11])
def test_loss_and_every_gradient_leaf_match_the_reference_in_float32(
        adapter, reference, seed, passes):
    sz = toy_sizes(adapter, total_ut_steps=passes)
    ref_params = reference.init_params(jax.random.PRNGKey(seed), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(seed + 1), 2, sz)
    model = OuroModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(ouro_loss_fn(model)),
                               adapter.to_program(ref_params, sz), ids)
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    # the exits add rows weighted 1 / N where the reference divides a sum by N: another order
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    want = adapter.to_program(ref_grads, sz, cast=False)
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for name, g in leaves_by_name(grads).items():
        w = leaves_by_name(want)[name]
        if passes == 1 and "exit_gate" in name:
            # one pass takes all the mass whatever its gate says
            assert not np.any(g) and not np.any(w), name
            continue
        assert np.linalg.norm(w) > 0, name
        assert rel_err(g, w) < 2e-4, (name, rel_err(g, w))
    # the output matrix is a leaf of its own, the gate one column and a bias
    assert adapter.HEAD_LEAF == "['lm_head']"
    assert grads["lm_head"].shape == (sz["hidden_size"], sz["vocab_size"])
    assert grads["exit_gate"].shape == (sz["hidden_size"],) and grads["exit_gate_bias"].shape == ()


def test_to_program_maps_the_references_tree_onto_the_programs_leaf_for_leaf(adapter, reference):
    sz = toy_sizes(adapter)
    ref_params = reference.init_params(jax.random.PRNGKey(0), sz)
    model = OuroModel(adapter.model_config(sz))
    ids = adapter.draw_batch(jax.random.PRNGKey(1), 1, sz)
    made = model.init(jax.random.PRNGKey(2), ids)["params"]
    mapped = adapter.to_program(ref_params, sz)
    assert jax.tree.structure(made) == jax.tree.structure(mapped)
    for name, leaf in leaves_by_name(made).items():
        assert leaf.shape == leaves_by_name(mapped)[name].shape and leaf.dtype == jnp.float32, name
    # one set of layers whatever the number of passes
    assert set(made) == {"embedding", "final_norm", "lm_head", "exit_gate", "exit_gate_bias",
                         "layer_0", "layer_1"}
    assert set(made["layer_0"]) == {"input_norm", "input_norm_2", "post_attention_norm",
                                    "post_attention_norm_2", "attn", "mlp"}


def test_the_seeded_gate_spreads_the_mass_over_the_passes(adapter, reference):
    """The benchmark's weights put the passes' mean shares apart and none under
    a tenth, so that a pass dropped or misweighted shows in the loss."""
    sz = toy_sizes(adapter)
    for seed in (0, 1, 2):
        params = adapter.to_program(reference.init_params(jax.random.PRNGKey(seed), sz), sz)
        ids = adapter.draw_batch(jax.random.PRNGKey(seed + 10), 4, sz)
        _, gates = compiled(lambda params, ids: OuroModel(
            adapter.model_config(sz, jnp.float32)).apply({"params": params}, ids), params, ids)
        shares = np.asarray(jnp.mean(exit_distribution(gates), axis=(1, 2)))
        assert shares.sum() == pytest.approx(1.0, abs=1e-5)
        assert shares.min() > 0.1 and np.min(np.abs(np.diff(np.sort(shares)))) > 0.01, shares


def test_the_config_is_built_from_the_published_keys():
    published = manifest.load_json("benchmark", "configs", "ouro-2.6b.json")
    cfg = OuroConfig.from_hf({**published, **published["published"]})
    assert cfg == OuroConfig()  # the defaults are the published model
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.vocab_size) == (48, 4, 49152)
    with pytest.raises(ValueError, match="layer_types"):
        OuroConfig(num_hidden_layers=4)
    with pytest.raises(NotImplementedError, match="use_sliding_window"):
        OuroConfig(use_sliding_window=True)
    with pytest.raises(ValueError, match="at least once"):
        ouro_test_config(total_ut_steps=0)


# -- the loop -----------------------------------------------------------------


def untied_loss(cfg, ids):
    """The model's loss with nothing shared: ``params[t]`` holds pass ``t``'s
    own copy of the layers, the final norm, the head and the gate."""
    targets = jnp.roll(ids, -1, axis=1)

    def loss_fn(embedding, passes):
        x = embedding[ids]
        entropies, gates = [], []
        for own in passes:
            for n in range(cfg.num_hidden_layers):
                x = OuroBlock(cfg).apply({"params": own[f"layer_{n}"]}, x)
            x = RMSNorm(cfg.rms_norm_eps).apply({"params": own["final_norm"]}, x)
            entropies.append(softmax_cross_entropy(x @ own["lm_head"], targets))
            gates.append(x @ own["exit_gate"] + own["exit_gate_bias"])
        p = exit_distribution(jnp.stack(gates))
        per_position = (jnp.sum(p * jnp.stack(entropies), axis=0)
                        - cfg.entropy_beta * distribution_entropy(p))
        return jnp.mean(per_position[:, :-1])

    return loss_fn


@pytest.mark.parametrize("layers,passes", [(2, 3), (1, 4), (3, 2)])
def test_the_loop_equals_an_untied_stack_and_a_shared_gradient_is_the_sum_of_its_copies(
        layers, passes):
    cfg = ouro_test_config(num_hidden_layers=layers, layer_types=("full_attention",) * layers,
                           total_ut_steps=passes)
    model = OuroModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(6), ids)["params"]
    params["embedding"] = 50.0 * params["embedding"]  # a stream of the size the norms give it
    params["exit_gate_bias"] = jnp.float32(-0.4)
    shared = {k: v for k, v in params.items() if k != "embedding"}
    with jax.default_matmul_precision("highest"):
        loss, grads = compiled(jax.value_and_grad(ouro_loss_fn(model)), params, ids)
        untied, (d_embedding, d_copies) = compiled(
            jax.value_and_grad(untied_loss(cfg, ids), argnums=(0, 1)),
            params["embedding"], [shared] * passes)
    assert float(loss) == pytest.approx(float(untied), abs=1e-6)
    assert rel_err(grads["embedding"], d_embedding) < 1e-5
    summed = jax.tree.map(lambda *copies: sum(copies), *d_copies)
    for name, g in leaves_by_name({k: v for k, v in grads.items() if k != "embedding"}).items():
        assert rel_err(g, leaves_by_name(summed)[name]) < 1e-5, name
    # and no copy's gradient is the whole: every pass visits the leaf
    first = leaves_by_name(d_copies[0])["['layer_0']['mlp']['down']"]
    assert rel_err(first, grads["layer_0"]["mlp"]["down"]) > 0.05


def test_the_next_pass_reads_the_normed_state_and_positions_start_over(adapter, reference):
    """Two things no key of ``config.json`` states, held by the reference: the
    hand-over between passes is the normed state, and the rotary embedding
    counts from position 0 in every pass."""
    sz = toy_sizes(adapter, total_ut_steps=2)
    params = reference.init_params(jax.random.PRNGKey(0), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(1), 2, sz)
    model = OuroModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    def reference_loss(params):
        return float(compiled(lambda p, ids: reference.loss(p, ids, sz), params, ids))

    got = float(compiled(ouro_loss_fn(model), adapter.to_program(params, sz), ids))
    assert got == pytest.approx(reference_loss(params), abs=2e-5)
    # the same weights under another scale of the final norm give another second pass
    scaled = {**params, "final_norm": 2.0 * params["final_norm"]}
    assert abs(reference_loss(scaled) - got) > 1e-3


# -- the exits ----------------------------------------------------------------


def oracle_distribution(gates):
    left, shares = 1.0, []
    for gate in gates[:-1]:
        shares.append(left * gate)
        left = left * (1.0 - gate)
    return shares + [left]


@pytest.mark.parametrize("logits", [
    [0.3, -1.2, 0.8, 2.0], [-30.0, -30.0, -30.0, -30.0], [30.0, 0.0, 0.0, 0.0],
    [-40.0, 40.0, 5.0, -5.0], [0.0], [1.5, -0.5]], ids=str)
def test_the_exit_distribution_equals_the_oracle_and_sums_to_one(logits):
    gate_logits = jnp.asarray(logits, jnp.float32)[:, None]
    p = np.asarray(compiled(exit_distribution, gate_logits))[:, 0]
    want = oracle_distribution([1.0 / (1.0 + np.exp(-np.float64(x))) for x in logits])
    np.testing.assert_allclose(p, want, atol=1e-6)
    assert p.sum() == pytest.approx(1.0, abs=1e-6) and np.all(p >= 0)
    # the last pass's own gate decides nothing
    moved = gate_logits.at[-1].set(7.0)
    np.testing.assert_array_equal(np.asarray(compiled(exit_distribution, moved))[:, 0], p)
    # the entropy and its gradient are finite where a share is exactly zero
    value, grad = compiled(jax.value_and_grad(
        lambda g: jnp.sum(distribution_entropy(exit_distribution(g)))), gate_logits)
    assert np.isfinite(float(value)) and np.all(np.isfinite(np.asarray(grad)))
    assert float(value) == pytest.approx(
        -sum(w * np.log(w) for w in want if w > 1e-30), abs=1e-5)


def test_one_pass_is_the_plain_cross_entropy():
    cfg = ouro_test_config(total_ut_steps=1)
    model = OuroModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(2), ids)["params"]
    logits, gates = compiled(lambda params, ids: model.apply({"params": params}, ids), params, ids)
    assert logits.shape == (1, 2, 16, cfg.vocab_size) and gates.shape == (1, 2, 16)
    p = compiled(exit_distribution, gates)
    np.testing.assert_array_equal(np.asarray(p), 1.0)
    np.testing.assert_array_equal(np.asarray(compiled(distribution_entropy, p)), 0.0)
    plain = compiled(lambda logits, ids: jnp.mean(
        softmax_cross_entropy(logits[0], jnp.roll(ids, -1, axis=1))[:, :-1]), logits, ids)
    assert float(compiled(ouro_loss_fn(model), params, ids)) == pytest.approx(float(plain), abs=1e-6)


def test_given_targets_the_model_returns_what_each_exit_adds_to_the_loss():
    cfg = ouro_test_config()
    model = OuroModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab_size)
    targets = jnp.roll(ids, -1, axis=1)
    params = model.init(jax.random.PRNGKey(4), ids)["params"]
    params["exit_gate_bias"] = jnp.float32(0.3)
    logits, gates = compiled(lambda params, ids: model.apply({"params": params}, ids), params, ids)
    sums, gates_again = compiled(
        lambda params, ids, targets: model.apply({"params": params}, ids, targets),
        params, ids, targets)
    assert sums.shape == (3,) and sums.dtype == jnp.float32 and gates.shape == (3, 2, 16)
    np.testing.assert_allclose(np.asarray(gates_again), np.asarray(gates), atol=1e-6)
    want = compiled(lambda gates, logits, targets: jnp.mean((
        exit_distribution(gates) * softmax_cross_entropy(logits, targets[None]))[:, :, :-1],
        axis=(1, 2)), gates, logits, targets)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(want), rtol=2e-6)
    # the mean's weights: one place, the last position of every sequence out
    weights = np.asarray(mean_weights((2, 16)))
    assert weights.shape == (2, 16) and not weights[:, -1].any()
    np.testing.assert_allclose(weights[:, :-1], 1.0 / 30, rtol=1e-7)


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_shares_taken_pass_by_pass_are_the_exit_distribution(passes):
    gate_logits = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 2, 16), jnp.float32)
    def pass_by_pass(gate_logits):
        left, shares = jnp.ones((2, 16), jnp.float32), []
        for t in range(passes):
            share, left = exit_share(gate_logits[t], left, last=t == passes - 1)
            shares.append(share)
        return jnp.stack(shares), left

    shares, left = compiled(pass_by_pass, gate_logits)
    np.testing.assert_allclose(
        np.asarray(shares), np.asarray(compiled(exit_distribution, gate_logits)), atol=1e-6)
    assert not np.asarray(left).any()  # the last pass leaves nothing
    # the first pass's share moves with its own gate, unless it is the last and takes everything
    moved = compiled(lambda gate: exit_share(gate + 1.0, jnp.ones((2, 16)), last=passes == 1)[0],
                     gate_logits[0])
    assert (passes == 1) == bool(np.all(np.asarray(moved) == np.asarray(shares[0])))


# -- the memory plan ----------------------------------------------------------


def plain_exit_sum(h, head, targets, weights):
    """What :func:`ouro._exit_sum` computes, as plain operations that autodiff
    differentiates as written: every exit's logits kept for the backward pass."""
    return h, jnp.sum(weights * softmax_cross_entropy(ouro._logits(h, head, h.dtype), targets))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_exits_three_gradients_are_autodiffs_of_the_plain_composition(dtype):
    """The normed state's, the output matrix's and the row weights', under a
    cotangent other than one (a scaled loss) with another on the state that
    goes on, rows of weight zero and a label outside the vocabulary."""
    rows, hidden, vocab = (2, 12), 32, 96
    keys = jax.random.split(jax.random.PRNGKey(17), 5)
    h = jax.random.normal(keys[0], rows + (hidden,), jnp.float32).astype(dtype)
    head = 0.3 * jax.random.normal(keys[1], (hidden, vocab), jnp.float32)
    targets = jax.random.randint(keys[2], rows, 0, vocab).at[0, 3].set(vocab + 5).at[1, 0].set(-1)
    weights = jax.random.uniform(keys[3], rows, jnp.float32).at[:, -1].set(0.0).at[0, 5].set(0.0)
    onward = jax.random.normal(keys[4], rows + (hidden,), jnp.float32)

    def scaled(exit_fn):
        def loss(h, head, weights):
            h_on, total = exit_fn(h, head, targets, weights)
            return 0.37 * total + jnp.sum(onward * h_on.astype(jnp.float32))
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(h, head, weights)

    (value, grads), (want_value, want) = scaled(ouro._exit_sum), scaled(plain_exit_sum)
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    assert [g.dtype for g in grads] == [dtype, jnp.float32, jnp.float32]
    for name, g, w in zip(("state", "output matrix", "row weights"), grads, want):
        assert g.shape == w.shape and rel_err(g, w) < BOUNDS[dtype], (name, rel_err(g, w))
    # the exit's own part of the state's gradient, without what came back from later passes
    own = np.asarray(grads[0], np.float32) - np.asarray(onward.astype(dtype), np.float32)
    assert not own[:, -1].any() and not own[0, 5].any() and own[0, 3].any()
    # a label outside picks nothing: the row reads its log-sum-exp, and so does its weight's gradient
    with jax.default_matmul_precision("highest"):
        lse = compiled(lambda h, head: jax.nn.logsumexp(ouro._logits(h, head, dtype), axis=-1),
                       h, head)
    np.testing.assert_allclose(np.asarray(grads[2])[[0, 1], [3, 0]],
                               0.37 * np.asarray(lse)[[0, 1], [3, 0]], rtol=1e-5)
    # differentiated or not, the same value
    assert float(compiled(ouro._exit_sum, h, head, targets, weights)[1]) == pytest.approx(
        float(compiled(plain_exit_sum, h, head, targets, weights)[1]), rel=1e-6)


def per_position_model(cfg, exit_fn):
    """The looped model in the form ``ouro_loss_fn`` also takes: given targets
    it returns every exit's cross entropies a position, from ``exit_fn(h, head,
    targets) -> (h, entropies)``, and the loss function weights them."""

    class PerPosition(OuroModel):
        @nn.compact
        def __call__(self, ids, targets):
            cfg = self.cfg
            x = ouro.embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids,
                           cfg.compute_dtype)
            layers = [OuroBlock(cfg, name=f"layer_{n}") for n in range(cfg.num_hidden_layers)]
            final_norm = RMSNorm(cfg.rms_norm_eps, name="final_norm")
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            w_exit = self.kernel("exit_gate", cfg.hidden_size)
            b_exit = self.param("exit_gate_bias", nn.initializers.zeros, (), jnp.float32)
            exits, gates = [], []
            for _ in range(cfg.total_ut_steps):
                for layer in layers:
                    x = layer(x)
                x, entropies = exit_fn(final_norm(x), head, targets)
                exits.append(entropies)
                gates.append(jnp.einsum("btm,m->bt", x.astype(jnp.float32), w_exit,
                                        precision=jax.lax.Precision.HIGHEST) + b_exit)
            return jnp.stack(exits), jnp.stack(gates)

    return PerPosition(cfg)


def logits_products(text, shape):
    """Products of the lowered ``text`` whose result is one exit's logits."""
    result = "-> tensor<" + "x".join(map(str, shape)) + "xf32> loc("
    return sum("dot_general" in line and result in line for line in text.splitlines())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_gradient_products_taken_at_the_exit_change_neither_loss_nor_gradient(
        monkeypatch, dtype):
    passes = 4
    cfg = ouro_test_config(total_ut_steps=passes, compute_dtype=dtype)
    model = OuroModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 24), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(8), ids)["params"]
    params["embedding"] = 50.0 * params["embedding"]
    params["exit_gate_bias"] = jnp.float32(-0.4)
    logits = (2, 24, cfg.vocab_size)

    def both(model):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(ouro_loss_fn(model)))(params, ids)

    def lowered(model):
        return jax.jit(jax.grad(ouro_loss_fn(model))).lower(params, ids).as_text(debug_info=True)

    loss, grads = both(model)
    text = lowered(model)
    # nothing is rebuilt: the head's product runs once an exit, forward and backward together
    assert "rematted_computation" not in text and "optimization_barrier" in text
    assert logits_products(text, logits) == passes
    # a version that keeps everything: the exit as plain operations, differentiated as written
    monkeypatch.setattr(ouro, "_exit_sum", plain_exit_sum)
    kept_text = lowered(model)
    assert "rematted_computation" not in kept_text and "optimization_barrier" not in kept_text
    assert logits_products(kept_text, logits) == passes
    kept_loss, kept_grads = both(model)
    # and the exit of three arguments, which builds every exit's logits a second time
    rebuilding = per_position_model(cfg, ouro._exit)
    rebuilt_text = lowered(rebuilding)
    assert any("rematted_computation" in line and "dot_general" in line
               for line in rebuilt_text.splitlines())
    assert logits_products(rebuilt_text, logits) == 2 * passes
    rebuilt_loss, rebuilt_grads = both(rebuilding)
    for other_loss, other_grads in ((kept_loss, kept_grads), (rebuilt_loss, rebuilt_grads)):
        assert float(loss) == pytest.approx(float(other_loss), rel=1e-6)
        for name, g in leaves_by_name(grads).items():
            # the same operations on the same operands: rounding of another order at most
            assert rel_err(g, leaves_by_name(other_grads)[name]) < BOUNDS[dtype], name


def test_a_model_of_per_position_entropies_and_the_exit_of_three_arguments_still_train():
    """What the benchmark's own tests build: a model whose ``__call__(ids,
    targets)`` returns ``(entropies (passes, batch, positions), gate logits)``
    from ``ouro._exit`` trains through ``ouro_loss_fn`` to the loss
    ``OuroModel`` trains to."""
    cfg = ouro_test_config()
    ids = jax.random.randint(jax.random.PRNGKey(9), (2, 16), 0, cfg.vocab_size)
    params = OuroModel(cfg).init(jax.random.PRNGKey(10), ids)["params"]
    model = per_position_model(cfg, ouro._exit)
    entropies, gates = compiled(
        lambda params, ids: model.apply({"params": params}, ids, jnp.roll(ids, -1, axis=1)),
        params, ids)
    assert entropies.shape == gates.shape == (3, 2, 16)
    want = float(compiled(ouro_loss_fn(OuroModel(cfg)), params, ids))
    assert float(compiled(ouro_loss_fn(model), params, ids)) == pytest.approx(want, rel=1e-6)
    step = jax.jit(lambda p: jax.tree.map(
        lambda x, g: x - 0.5 * g, p, jax.grad(ouro_loss_fn(model))(p, ids)))
    for _ in range(3):
        params = step(params)
    after = float(compiled(ouro_loss_fn(OuroModel(cfg)), params, ids))
    assert np.isfinite(after) and after < want - 0.05


# -- the engine on four devices -----------------------------------------------


def engine(overlap, loss_fn, group, lr):
    return DistributedDataParallel(
        loss_fn, optax.sgd(lr), GradientAllReduceAlgorithm(), process_group=group,
        bucket_size_bytes=1 << 12,  # small: several buckets
        overlap=overlap)


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "after_backward"])
def test_four_devices_one_exchange_a_bucket_of_the_summed_gradient(adapter, reference, overlap):
    """A weight visited four times has one cotangent, the sum, and
    ``wrap_params_for_overlap`` hangs one exchange on it: not one a visit, and
    not an exchange of a partial sum.  The step's gradient is the plain
    reference's of the global batch."""
    sz = toy_sizes(adapter)
    lr = 0.5
    group = bagua_tpu.init_process_group(devices=jax.devices()[:4])
    ref_params = reference.init_params(jax.random.PRNGKey(21), sz)
    ids = adapter.draw_batch(jax.random.PRNGKey(22), 4, sz)  # a sequence a device
    model = OuroModel(adapter.model_config(sz, compute_dtype=jnp.float32))
    ddp = engine(overlap, ouro_loss_fn(model), group, lr)
    start = adapter.to_program(ref_params, sz)
    with jax.default_matmul_precision("highest"):
        state = ddp.init(start)
        assert ddp.overlap_enabled is overlap and ddp.plan.num_buckets > 4
        program, _ = collect_ir(ddp._build_sharded("default"), (_abstract(state), _abstract(ids)),
                                dict(group.mesh.shape))
        state, losses = ddp.train_step(state, ddp.shard_batch(ids))
        ref_loss, ref_grads = compiled(
            jax.value_and_grad(lambda p, ids: reference.loss(p, ids, sz)), ref_params, ids)
    # one labelled exchange a bucket in either mode: the tuple fuse's one variadic psum is an
    # equation a leaf in the jaxpr, so a bucket of n leaves reads n, each of a whole leaf, once
    exchanges = program.by_bucket_phase()
    assert sorted(bucket for _, bucket, _ in exchanges) == list(range(ddp.plan.num_buckets))
    for (algo, bucket, phase), calls in exchanges.items():
        assert algo == "gradient_allreduce" and phase == ("overlap" if overlap else "mono")
        slots = ddp.plan.specs[bucket].slots
        assert [c.shapes for c in calls] == [(tuple(slot.shape),) for slot in slots], bucket
        assert all(c.primitive == "psum" and c.ring_size == 4 for c in calls)
        assert all(("bagua_overlap_bwd/bucket=%d" % bucket in c.label) is overlap for c in calls)
    # nothing crosses the ranks unlabelled, and nothing crosses them four times
    assert len(program.collectives) == len(program.labeled()) == len(leaves_by_name(start))
    # every shared leaf sits in one bucket once
    slots = [slot.name for spec in ddp.plan.specs for slot in spec.slots]
    assert sorted(slots) == sorted(leaves_by_name(start))
    assert float(np.mean(np.asarray(losses))) == pytest.approx(float(ref_loss), abs=2e-6)
    want = leaves_by_name(adapter.to_program(ref_grads, sz, cast=False))
    for name, before in leaves_by_name(start).items():
        after = leaves_by_name(state.params)[name]
        assert np.all(np.asarray(after) == np.asarray(after[:1])), name  # the ranks agree
        # read back from the update, so to the rounding of a weight of size one less lr x g
        grad = (np.asarray(before) - np.asarray(after[0])) / lr
        assert rel_err(grad, want[name]) < 2e-3, (name, rel_err(grad, want[name]))


# -- scopes, passes and the summary's classes ---------------------------------


def test_every_part_and_every_pass_is_named_in_both_passes_of_autodiff():
    cfg = ouro_test_config()
    model = OuroModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    text = jax.jit(jax.grad(ouro_loss_fn(model))).lower(params, ids).as_text(debug_info=True)
    for part in PARTS:
        label = format_model_label(part)
        assert label in text, part
        assert any("transpose(" in line for line in text.splitlines() if label in line), part
    for run in (1, 2, 3):
        label = format_pass_label(run) + "/"
        assert any("transpose(" in line for line in text.splitlines() if label in line), run
    assert format_pass_label(4) not in text and format_pass_label(0) not in text
    for absent in ("moe_route", "attn_window_core", "conv_core"):
        assert format_model_label(absent) not in text
    # in the forward pass: the lookup under no pass, then each pass's layers, head and gate
    names = [str(eqn.source_info.name_stack)
             for eqn in jax.make_jaxpr(ouro_loss_fn(model))(params, ids).eqns]
    seen = [(parse_model_pass(n), parse_model_part(n)) for n in names]
    order = [s for s, before in zip(seen[1:], seen) if s != before and s[1]]
    layer = ["attn_proj", "attn_core", "attn_proj", "dense_mlp"]
    assert order == [(None, "embed")] + [
        # the gate before the head: a pass's share of the mass is an input of its exit
        (run, part) for run in (1, 2, 3) for part in layer * 2 + ["exit_gate", "head"]
    ] + [(None, "exit_gate")]


def test_the_grammar_reads_a_pass_beside_a_part():
    name = ("jit(step)/bagua_step/phase=fwd_bwd/jvp(OuroModel)/bagua_model/pass=3/layer_1/attn/"
            "bagua_model/part=attn_core/pallas_call")
    assert (parse_model_pass(name), parse_model_part(name)) == (3, "attn_core")
    assert parse_model_pass("jit(step)/bagua_model/part=head/dot") is None
    assert parse_model_part("jit(step)/jvp(bagua_model/part=exit_gate)/div") == "exit_gate"

    def inside(x):
        with pass_scope(2):
            return x + 1

    traced = jax.make_jaxpr(inside)(1.0)
    assert format_pass_label(2) == "bagua_model/pass=2" in str(traced.eqns[0].source_info.name_stack)
    assert ta.phase_of("bagua_step/phase=fwd_bwd/transpose(jvp(m))/checkpoint/"
                       "rematted_computation/dot") == "recompute"
    assert ta.phase_of("bagua_step/phase=fwd_bwd/transpose(jvp(m))/checkpoint/dot") == "backward"
    assert ta.phase_of("bagua_step/phase=optimizer/rematted_computation") == "optimizer"


def test_the_summary_reads_recompute_and_the_passes(tmp_path):
    """``recompute`` is a class of the partition beside ``forward`` and
    ``backward``, ``model_part_ms`` holds all three, ``model_pass_ms`` gives
    them by pass, and a capture with neither label reads as it did."""
    fwd = "bagua_step/phase=fwd_bwd/jvp(OuroModel)"
    bwd = "bagua_step/phase=fwd_bwd/transpose(jvp(OuroModel))"
    ops = []

    def op(n, start, end, op_name):
        ops.append((f"%fusion.{n} = f32[4] fusion()", 1000 * (1000 + start), 1000 * (end - start),
                    {"op_name": op_name}))

    op(1, 0, 2, fwd + "/bagua_model/part=embed/gather")
    op(2, 2, 12, fwd + "/bagua_model/pass=1/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(3, 12, 16, fwd + "/bagua_model/pass=1/layer_0/bagua_model/part=dense_mlp/mlp/dot_general")
    op(4, 16, 20, fwd + "/bagua_model/pass=1/bagua_model/part=head/checkpoint/dot_general")
    op(5, 20, 30, fwd + "/bagua_model/pass=2/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(6, 30, 31, fwd + "/bagua_model/pass=2/final_norm/mul")
    op(7, 31, 35, fwd + "/bagua_model/pass=2/bagua_model/part=head/checkpoint/dot_general")
    op(8, 35, 36, "bagua_step/phase=fwd_bwd/jvp(bagua_model/part=exit_gate)/div")
    op(9, 36, 41, bwd + "/bagua_model/pass=2/bagua_model/part=head/jvp()/checkpoint/"
       "rematted_computation/dot_general")
    op(10, 41, 49, bwd + "/bagua_model/pass=2/bagua_model/part=head/jvp()/checkpoint/dot_general")
    op(11, 49, 69, bwd + "/bagua_model/pass=2/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(12, 69, 74, bwd + "/bagua_model/pass=1/bagua_model/part=head/jvp()/checkpoint/"
       "rematted_computation/dot_general")
    op(13, 74, 94, bwd + "/bagua_model/pass=1/layer_0/attn/bagua_model/part=attn_core/pallas_call")
    op(14, 94, 96, bwd + "/bagua_model/part=embed/tgmm")
    modules = [("jit_local_step(1)", 1000 * 1000, 1000 * 96, {})]
    path = str(tmp_path / "passes.xplane.pb")
    with open(path, "wb") as f:
        f.write(xspace_bytes([("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, ops)])]))
    got = ta.summarize_capture(path)
    ms = pytest.approx
    assert got["partition_ms"] == {"forward": ms(0.036), "backward": ms(0.050),
                                   "recompute": ms(0.010)}
    assert got["model_part_ms"] == {
        "embed": ms(0.004), "attn_core": ms(0.060), "dense_mlp": ms(0.004), "head": ms(0.026),
        "exit_gate": ms(0.001), "other": ms(0.001)}
    assert sum(got["model_part_ms"].values()) == ms(sum(got["partition_ms"].values()))
    assert got["model_pass_ms"] == {"1": ms(0.043), "2": ms(0.048)}
    # the lookup and the loss that joins the exits run under no pass
    assert sum(got["partition_ms"].values()) - sum(got["model_pass_ms"].values()) == ms(0.005)
    assert got["layer_applications_per_step"] == 2
    # a model that names parts and no pass, and rebuilds nothing, reads as it did
    plain = [(text, ts, dur, {"op_name": re.sub(r"bagua_model/pass=\d/", "", stats["op_name"])})
             for text, ts, dur, stats in ops if "rematted_computation" not in stats["op_name"]]
    with open(path, "wb") as f:
        f.write(xspace_bytes([("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, plain)])]))
    got = ta.summarize_capture(path)
    assert set(got["partition_ms"]) == {"forward", "backward"}
    assert "model_pass_ms" not in got and "layer_applications_per_step" not in got
    assert sum(got["model_part_ms"].values()) == ms(sum(got["partition_ms"].values()))
