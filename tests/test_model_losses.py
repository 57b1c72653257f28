"""``models.losses.softmax_cross_entropy``: the cross-entropy written from the
logits (log-sum-exp minus the logit a one-hot select picks) against the form
it replaced in the five model files, ``log_softmax`` then ``take_along_axis``,
which is kept here as the reference.  Both sides of every comparison run
compiled (``helpers.compiled``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bagua_tpu.models.losses import softmax_cross_entropy
from helpers import compiled

DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])

# Set from the dtype beforehand.  f32: the two orders of the two subtractions
# differ by an ulp of ``max + log(sum)`` (value), and ``exp(x - log s)``
# against ``exp(x) / s`` by an ulp of the exponent (gradient).  bf16: an ulp
# under 16, where ``max + log(sum)`` stays, is 2**-4, so two are 0.125.
VALUE_TOL = {jnp.float32: dict(rtol=1e-6, atol=1e-6), jnp.bfloat16: dict(rtol=0, atol=0.125)}
GRAD_TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-10), jnp.bfloat16: dict(rtol=2 ** -5, atol=2 ** -12)}


def reference_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def draw(shape, dtype, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(*shape).astype(np.float32) * 2.0).astype(dtype)
    labels = jnp.asarray(rng.randint(0, shape[-1], shape[:-1]).astype(np.int32))
    return logits, labels


def assert_close(got, want, tol):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)


@DTYPES
@pytest.mark.parametrize("shape", [(4, 10), (2, 7, 33), (8, 16, 30522), (1, 64, 1031)], ids=str)
def test_value_and_gradient_equal_the_log_softmax_form(shape, dtype):
    logits, labels = draw(shape, dtype)
    weights = jnp.asarray(np.random.RandomState(1).rand(*shape[:-1]).astype(np.float32))

    def weighted(fn):
        return lambda lg: jnp.sum(fn(lg, labels).astype(jnp.float32) * weights)

    got = compiled(softmax_cross_entropy, logits, labels)
    assert got.dtype == dtype and got.shape == shape[:-1]
    assert_close(got, compiled(reference_cross_entropy, logits, labels), VALUE_TOL[dtype])
    assert_close(compiled(jax.grad(weighted(softmax_cross_entropy)), logits),
                 compiled(jax.grad(weighted(reference_cross_entropy)), logits), GRAD_TOL[dtype])


@DTYPES
@pytest.mark.parametrize("case", ["plus_minus_80", "one_logit_minus_inf"])
def test_loss_and_gradient_stay_finite_at_extreme_logits(case, dtype):
    rng = np.random.RandomState(3)
    if case == "plus_minus_80":  # exp(-160) underflows; the label may sit at -80
        logits = np.where(rng.rand(6, 12) < 0.5, -80.0, 80.0).astype(np.float32)
        logits[:, 0], logits[:, 1] = 80.0, -80.0
        labels = np.array([0, 1, 0, 1, 5, 7], np.int32)
    else:  # a masked-out class: never the label
        logits = rng.randn(6, 12).astype(np.float32)
        logits[:, 4] = -np.inf
        labels = np.array([0, 1, 2, 3, 5, 11], np.int32)
    logits, labels = jnp.asarray(logits).astype(dtype), jnp.asarray(labels)
    loss, grad = compiled(jax.value_and_grad(
        lambda lg: jnp.mean(softmax_cross_entropy(lg, labels).astype(jnp.float32))), logits)
    assert np.isfinite(float(loss))
    assert np.all(np.isfinite(np.asarray(grad, np.float32)))
    # ``max + log(sum)`` reaches 162 here and the form rounds there: the
    # value's absolute error is an ulp of the row maximum, not of the loss
    top = 128 if case == "plus_minus_80" else 4
    assert_close(compiled(softmax_cross_entropy, logits, labels),
                 compiled(reference_cross_entropy, logits, labels),
                 dict(rtol=0, atol=2 * float(jnp.finfo(dtype).eps) * top))
    if case == "one_logit_minus_inf":
        assert np.all(np.asarray(grad, np.float32)[:, 4] == 0.0)


@DTYPES
@pytest.mark.parametrize("label", [0, -1], ids=["first", "last"])
def test_labels_at_both_ends_of_the_vocabulary(label, dtype):
    vocab = 97
    logits, _ = draw((5, vocab), dtype, seed=4)
    labels = jnp.full((5,), label % vocab, jnp.int32)
    got = compiled(softmax_cross_entropy, logits, labels)
    assert_close(got, compiled(reference_cross_entropy, logits, labels), VALUE_TOL[dtype])
    # the picked entry is that column and no neighbour's
    want = compiled(lambda lg: jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
                    - lg[:, label].astype(jnp.float32), logits)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), **VALUE_TOL[dtype])


def one_sequence(dtype, positions=48, vocab=131):
    """A ``[1, T, V]`` batch as the expert models' losses see it: the labels
    are the ids rolled by one, both ends of the vocabulary are among them, and
    one label stands three times running."""
    logits, ids = draw((1, positions, vocab), dtype, seed=5)
    ids = ids.at[0, 3].set(0).at[0, 9].set(vocab - 1).at[0, 20:23].set(77)
    return logits, jnp.roll(ids, -1, axis=1)


@DTYPES
def test_next_token_mean_over_one_sequence_equals_the_log_softmax_form(dtype):
    """The mean the expert models take: every row against the token after it,
    the last row (whose label is the roll's wrap-around) left out."""
    logits, labels = one_sequence(dtype)
    assert {0, logits.shape[-1] - 1} <= set(np.asarray(labels[0, :-1]).tolist())
    assert np.asarray(labels)[0, 19:22].tolist() == [77, 77, 77]

    def mean_of(fn):
        return lambda lg: jnp.mean(fn(lg, labels)[:, :-1].astype(jnp.float32))

    got, got_grad = compiled(jax.value_and_grad(mean_of(softmax_cross_entropy)), logits)
    want, want_grad = compiled(jax.value_and_grad(mean_of(reference_cross_entropy)), logits)
    # a mean of 47 values, each within VALUE_TOL
    assert_close(got, want, VALUE_TOL[dtype])
    assert_close(got_grad, want_grad, GRAD_TOL[dtype])
    assert np.all(np.asarray(got_grad, np.float32)[0, -1] == 0.0)  # the row left out


@pytest.mark.parametrize("reduce", ["sum", "next_token_mean"])
def test_gradient_is_softmax_minus_onehot_in_f32(reduce):
    """The select's gradient is ``where(hit, g, 0)``: it takes ``g`` from the
    label's entry of the log-sum-exp's own gradient, ``g * softmax``, and
    touches no other, bit for bit."""
    logits, labels = one_sequence(jnp.float32)
    positions, vocab = logits.shape[1:]
    kept = np.ones((1, positions, 1), np.float32)
    g = np.float32(1.0)
    if reduce == "next_token_mean":
        kept[:, -1] = 0.0
        g = np.float32(1.0 / (positions - 1))

    def total(fn):
        return lambda lg: jnp.sum(fn(lg) * kept[..., 0]) * g

    grad = np.asarray(compiled(
        jax.grad(total(lambda lg: softmax_cross_entropy(lg, labels))), logits))
    lse_grad = np.asarray(compiled(
        jax.grad(total(lambda lg: jax.nn.logsumexp(lg, axis=-1))), logits))
    onehot = kept * np.asarray(jax.nn.one_hot(labels, vocab, dtype=jnp.float32))
    np.testing.assert_array_equal(grad, lse_grad - g * onehot)
    softmax = np.asarray(compiled(lambda lg: jax.nn.softmax(lg, axis=-1), logits))
    np.testing.assert_allclose(grad, g * (kept * softmax - onehot), rtol=1e-5, atol=1e-9)
    # each kept row's gradient sums to zero: one whole softmax less one
    np.testing.assert_allclose(grad.sum(axis=-1), 0.0, atol=1e-6)


# -- the five model loss functions give what they gave --------------------------


def _mlp():
    from bagua_tpu.models.mlp import init_mlp, mlp_apply, softmax_loss

    rng = np.random.RandomState(0)
    params = init_mlp(jax.random.PRNGKey(0), [8, 16, 5])
    batch = (jnp.asarray(rng.randn(6, 8).astype(np.float32)),
             jnp.asarray(rng.randint(0, 5, 6).astype(np.int32)))
    want = compiled(lambda params, batch: jnp.mean(
        reference_cross_entropy(mlp_apply(params, batch[0]), batch[1])), params, batch)
    return compiled(softmax_loss, params, batch), want


def _vgg():
    from bagua_tpu.models.vgg import VGG, vgg_loss_fn

    rng = np.random.RandomState(0)
    model = VGG(num_classes=10, cfg=(8, "M", 16, "M"), classifier_width=32)
    x = jnp.asarray(rng.randn(4, 8, 8, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, 4).astype(np.int32))
    params = compiled(model.init, jax.random.PRNGKey(0), x)["params"]
    want = compiled(lambda params, x, y: jnp.mean(
        reference_cross_entropy(model.apply({"params": params}, x), y)), params, x, y)
    return compiled(vgg_loss_fn(model), params, (x, y)), want


def _resnet():
    from bagua_tpu.models.resnet import ResNet, resnet_loss_fn

    rng = np.random.RandomState(0)
    model = ResNet([1, 1], num_classes=10)
    x = jnp.asarray(rng.randn(4, 16, 16, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, 4).astype(np.int32))
    variables = compiled(model.init, jax.random.PRNGKey(0), x)
    want = compiled(lambda variables, x, y: jnp.mean(reference_cross_entropy(
        model.apply(variables, x, mutable=["batch_stats"])[0], y)), variables, x, y)
    return compiled(resnet_loss_fn(model), variables, (x, y)), want


def _bert():
    from bagua_tpu.models.bert import BertConfig, BertForPreTraining, mlm_loss_fn

    rng = np.random.RandomState(0)
    cfg = BertConfig(vocab_size=61, hidden_size=16, num_layers=1, num_heads=2,
                     intermediate_size=32, max_position_embeddings=8)
    model = BertForPreTraining(cfg)
    ids = jnp.asarray(rng.randint(0, 61, (3, 8)).astype(np.int32))
    labels = jnp.asarray(rng.randint(0, 61, (3, 8)).astype(np.int32))
    params = compiled(model.init, jax.random.PRNGKey(0), ids)["params"]
    want = compiled(lambda params, ids, labels: jnp.mean(reference_cross_entropy(
        model.apply({"params": params}, ids), labels)), params, ids, labels)
    return compiled(mlm_loss_fn(model), params, (ids, labels)), want


def _gpt_config(**kw):
    from bagua_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=32, hidden_size=16, num_heads=4, num_layers=1,
                     max_position_embeddings=16, **kw)


def _gpt():
    from bagua_tpu.models.gpt import GPTModel, lm_loss_fn

    model = GPTModel(_gpt_config())
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 32, (2, 16)).astype(np.int32))
    params = compiled(model.init, jax.random.PRNGKey(0), ids)["params"]
    want = compiled(lambda params, ids: jnp.mean(reference_cross_entropy(
        model.apply({"params": params}, ids)[:, :-1], ids[:, 1:])), params, ids)
    return compiled(lm_loss_fn(model), params, ids), want


def _gpt_zigzag():
    """Sequence-parallel over four ranks in the zigzag layout: each rank's
    loss leaves out its mid-block seam pair."""
    from bagua_tpu.models.gpt import GPTModel, lm_loss_fn

    sp, t_local = 4, 4
    cfg = _gpt_config(sp_axis="sp", sp_layout="zigzag")
    model = GPTModel(cfg)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 32, (2, sp * t_local)).astype(np.int32))
    local = GPTModel(dataclasses.replace(cfg, sp_axis=None, sp_layout="contiguous"))
    params = compiled(local.init, jax.random.PRNGKey(0), ids)["params"]
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))

    def per_rank(fn):
        return jax.jit(jax.shard_map(
            lambda ii: fn(ii)[None], mesh=mesh, in_specs=P(None, "sp"),
            out_specs=P("sp"), check_vma=False))(ids)

    def reference(ii):
        logits = model.apply({"params": params}, ii)
        nll = reference_cross_entropy(logits[:, :-1], ii[:, 1:])
        keep = jnp.arange(t_local - 1) != (t_local // 2 - 1)
        return jnp.sum(nll * keep[None]) / (nll.shape[0] * (t_local - 2))

    loss_fn = lm_loss_fn(model)
    return per_rank(lambda ii: loss_fn(params, ii)), per_rank(reference)


MODEL_LOSSES = {"mlp": _mlp, "vgg": _vgg, "resnet": _resnet, "bert": _bert,
                "gpt": _gpt, "gpt_zigzag": _gpt_zigzag}


@pytest.mark.parametrize("name", sorted(MODEL_LOSSES))
def test_model_loss_gives_the_value_the_log_softmax_form_gave(name):
    got, want = MODEL_LOSSES[name]()
    assert np.all(np.isfinite(np.asarray(got)))
    assert_close(got, want, VALUE_TOL[jnp.float32])
