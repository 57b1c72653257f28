"""``models.embedding.embed``: the one lookup of the three expert models and
its hand-written gradient, against ``zeros.at[ids].add`` in float32, which is
what autodiff made of ``table[ids]`` and is kept here as the reference.  The
grouped form the chip takes runs here through Pallas' interpreter.  Both sides
of every comparison run compiled (``helpers.compiled``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models import embedding
from bagua_tpu.models.embedding import embed, grouped_table_gradient, grouped_tiling
from helpers import compiled

DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
HIDDEN = 128


def draw(vocab, shape, dtype, seed=0, repeated=None):
    """A table, ids that hold 0 and ``vocab - 1`` (and ``repeated`` a hundred
    times), and a cotangent in ``dtype``."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, shape).astype(np.int32)
    flat = ids.reshape(-1)
    flat[0], flat[-1] = vocab - 1, 0
    if repeated is not None:
        flat[rng.permutation(flat.size - 2)[:100] + 1] = repeated
    table = jnp.asarray(rng.randn(vocab, HIDDEN).astype(np.float32))
    g = jnp.asarray(rng.randn(*shape, HIDDEN).astype(np.float32)).astype(dtype)
    return table, jnp.asarray(ids), g


def reference_gradient(g, ids, vocab):
    return jnp.zeros((vocab, g.shape[-1]), jnp.float32).at[ids.reshape(-1)].add(
        g.reshape(-1, g.shape[-1]).astype(jnp.float32))


def through_the_interpreter(monkeypatch, tiles):
    """Steer ``embed``'s backward pass to the chip's form, run by Pallas'
    interpreter: in the test, as ``tests/test_causal_attention.py`` reaches the chip's
    attention."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(embedding, "grouped_tiling", lambda _: tiles)
    grouped = embedding.grouped_table_gradient
    monkeypatch.setattr(embedding, "grouped_table_gradient",
                        lambda *args: grouped(*args, interpret=True))


@DTYPES
@pytest.mark.parametrize("shape", [(1, 300), (3, 100)], ids=str)
@pytest.mark.parametrize("vocab", [1031, 1024])
def test_value_and_gradient_equal_the_gather_and_its_scatter_add(vocab, shape, dtype):
    table, ids, g = draw(vocab, shape, dtype, repeated=7)
    def both_passes(table, ids, g):
        rows, vjp = jax.vjp(lambda t: embed(t, ids, dtype), table)
        return rows, vjp(g)[0]

    rows, grad = compiled(both_passes, table, ids, g)
    assert rows.dtype == dtype and rows.shape == shape + (HIDDEN,)
    np.testing.assert_array_equal(
        np.asarray(rows, np.float32),
        np.asarray(compiled(lambda table, ids: table[ids].astype(dtype), table, ids), np.float32))
    assert grad.dtype == table.dtype and grad.shape == table.shape
    # the same float32 additions in the same order: the scatter-add itself
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(compiled(
        lambda g, ids: reference_gradient(g, ids, vocab), g, ids)))
    assert np.abs(np.asarray(grad[7])).max() > 0 and np.abs(np.asarray(grad[vocab - 1])).max() > 0


@DTYPES
@pytest.mark.parametrize("vocab,tiles", [
    (1031, (128, 256, 128)),  # no block divides it: five blocks, the last holds 7 rows
    (1024, (128, 256, 128)),  # four whole blocks
    (1031, (64, 128, 128)),   # nine blocks, some of them empty at 40 tokens
], ids=["1031-b256", "1024-b256", "1031-b128"])
@pytest.mark.parametrize("shape", [(1, 300), (2, 20)], ids=str)
def test_grouped_form_equals_the_scatter_add(vocab, tiles, shape, dtype):
    """Blocks, group sizes, the fill up to whole token tiles, the visit that
    zeroes an empty block and the slice to ``vocab`` rows, held off the chip:
    300 and 40 tokens are no multiple of a token tile."""
    _, ids, g = draw(vocab, shape, dtype, seed=1, repeated=vocab // 2)
    got = compiled(lambda g, ids: grouped_table_gradient(
        g.reshape(-1, HIDDEN), ids.reshape(-1), vocab, tiles, interpret=True), g, ids)
    want = compiled(lambda g, ids: reference_gradient(g, ids, vocab), g, ids)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # a hundred repeats sum in another order: an ulp of the sum's largest partial
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=2e-5)
    untouched = np.setdiff1d(np.arange(vocab), np.asarray(ids))
    assert untouched.size and not np.asarray(got)[untouched].any()


@pytest.mark.parametrize("vocab", [300, 256])  # row ``vocab`` in the last block, and in none
def test_an_id_outside_the_vocabulary_picks_nothing_in_either_form(vocab):
    table, ids, g = draw(vocab, (1, 64), jnp.float32, seed=2)
    outside = ids.at[0, 5].set(-1).at[0, 6].set(vocab).at[0, 7].set(vocab + 700)
    inside = np.ones(64, bool)
    inside[5:8] = False
    want = compiled(lambda g, ids: reference_gradient(g, ids, vocab), g[:, inside], ids[:, inside])
    (plain,) = compiled(lambda table, ids, g: jax.vjp(
        lambda t: embed(t, ids, jnp.float32), table)[1](g), table, outside, g)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(want))
    grouped = compiled(lambda g, ids: grouped_table_gradient(
        g, ids, vocab, (64, 128, 128), interpret=True), g[0], outside[0])
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("form", ["plain", "grouped"])
def test_a_tied_table_sums_the_lookups_gradient_and_the_heads(form, monkeypatch):
    """``models/lfm2_moe.py``: the lookup and the output matrix on one leaf."""
    vocab = 1031
    table, ids, _ = draw(vocab, (2, 24), jnp.float32, seed=3)
    mix = jnp.asarray(np.random.RandomState(4).randn(HIDDEN, HIDDEN).astype(np.float32) / 12)

    def loss(lookup, emb_table, head_table):
        x = jnp.tanh(lookup(emb_table).astype(jnp.float32) @ mix)
        return jnp.mean(jax.nn.logsumexp(jnp.einsum("btm,vm->btv", x, head_table), axis=-1))

    def plain(t):
        return t[ids].astype(jnp.bfloat16)

    of_lookup, of_head = compiled(
        jax.grad(lambda a, b: loss(plain, a, b), argnums=(0, 1)), table, table)
    if form == "grouped":
        through_the_interpreter(monkeypatch, (16, 256, 128))
    tied = compiled(jax.grad(lambda t: loss(lambda u: embed(u, ids, jnp.bfloat16), t, t)), table)
    np.testing.assert_allclose(np.asarray(tied), np.asarray(of_lookup + of_head),
                               rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(of_lookup).max()) > 0 and float(jnp.abs(of_head).max()) > 0


@pytest.mark.parametrize("hidden", [2560, 2048, 384])
def test_the_tiling_comes_from_the_shapes_and_its_tiles_divide_them(hidden):
    token_tile, block, columns = grouped_tiling(hidden)
    assert block % 128 == 0
    assert token_tile % 16 == 0 and columns % 128 == 0 and hidden % columns == 0
    # the result, the float32 accumulator and the two operands' tiles, the
    # moving ones twice: inside a kernel's 16 MB of fast memory
    assert (3 * block * columns * 4 + 2 * token_tile * (block + columns) * 2) < 12 * 2 ** 20


@pytest.mark.parametrize("family", ["glm", "lfm2", "smallthinker"])
def test_the_three_expert_models_look_their_tokens_up_here(family):
    """The counter that says the lookup engages is its part name: each
    model's gradient carries ``bagua_model/part=embed`` in both passes (GLM's
    with the prediction module's second lookup), and rows no token named keep
    a zero gradient unless the table is the output matrix too."""
    from bagua_tpu.models import glm_moe, lfm2_moe, smallthinker_moe
    from bagua_tpu.observability.annotations import format_model_label

    cfg, model_cls, make_loss = {
        "glm": (glm_moe.glm_moe_test_config(), glm_moe.GlmMoeModel, glm_moe.glm_moe_loss_fn),
        "lfm2": (lfm2_moe.lfm2_moe_test_config(), lfm2_moe.Lfm2MoeModel, lfm2_moe.lfm2_moe_loss_fn),
        "smallthinker": (smallthinker_moe.smallthinker_test_config(), smallthinker_moe.SmallThinkerModel,
                         smallthinker_moe.smallthinker_loss_fn)}[family]
    model = model_cls(cfg)
    loss_fn = make_loss(model)
    ids = jnp.asarray(np.random.RandomState(5).randint(0, cfg.vocab_size, (2, 16)), jnp.int32)
    params = compiled(model.init, jax.random.PRNGKey(0), ids)["params"]
    text = jax.jit(jax.grad(loss_fn)).lower(params, ids).as_text(debug_info=True)
    labelled = [line for line in text.splitlines() if format_model_label("embed") in line]
    assert labelled and any("transpose(" in line for line in labelled)
    grad = np.asarray(compiled(jax.grad(loss_fn), params, ids)["embedding"])
    assert np.abs(grad[np.unique(np.asarray(ids))]).max() > 0
    if "lm_head" in params:
        unseen = np.setdiff1d(np.arange(cfg.vocab_size), np.asarray(ids))
        assert unseen.size and not grad[unseen].any()
