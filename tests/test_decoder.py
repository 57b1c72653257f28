"""``models/decoder.py``: the parts the decoder models share, each against a
plain oracle, once and compiled (``helpers.compiled``): ``RMSNorm``,
``rotate_half`` against the rotation of each pair as a complex number,
``shift``, ``SwiGLU``, the next-token loss against a slice then a mean, and
the grouped-query attention layer at each of the settings a model builds it
with, value and every gradient leaf, against quadratic attention on the
layer's own projections.  And the module's place among the model files: who
imports what, pinned in a subprocess."""

import ast
import inspect
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models import decoder, smallthinker_moe
from bagua_tpu.models.decoder import (
    GroupedQueryAttention,
    RMSNorm,
    RotaryTables,
    SwiGLU,
    next_token_loss_fn,
    rotary,
    rotate_half,
    shift,
)
from helpers import REPO_ROOT, compiled, worker_env
from oracles import quadratic_attention, rel_err

MODELS = ("glm_moe", "lfm2_moe", "smallthinker_moe", "ouro", "nemotron_h", "laguna")
#: what a model file needs none of, and every one of them loaded until PR 48
NOT_A_DECODERS = ("bagua_tpu.models.llama", "bagua_tpu.models.gpt",
                  "bagua_tpu.parallel.ring_attention", "bagua_tpu.parallel.tensor_parallel")


# -- the small parts ------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rms_norm_divides_by_the_root_mean_square_in_float32_and_keeps_the_type(dtype):
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 5, 24))).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (24,))
    assert jax.tree.map(jnp.shape, RMSNorm().init(jax.random.PRNGKey(2), x)) == {
        "params": {"scale": (24,)}}
    got = compiled(lambda scale, x: RMSNorm(1e-3).apply({"params": {"scale": scale}}, x), scale, x)
    exact = np.asarray(x, np.float64)
    want = exact / np.sqrt(np.mean(exact ** 2, axis=-1, keepdims=True) + 1e-3) * np.asarray(scale)
    assert got.dtype == dtype
    assert rel_err(got, want) < (1e-6 if dtype == jnp.float32 else 4e-3)  # one rounding to bf16


def test_rotate_half_pairs_column_i_with_column_i_plus_half():
    t, size, theta = 6, 8, 1e4
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, t, size))
    got = compiled(lambda x: rotate_half(x, theta, 0.5), x)
    for i in range(size // 2):
        angle = np.arange(t) * theta ** (-2 * i / size)
        a, b = np.asarray(x[..., i]), np.asarray(x[..., i + size // 2])
        np.testing.assert_allclose(got[..., i], 0.5 * (a * np.cos(angle) - b * np.sin(angle)),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(got[..., i + size // 2],
                                   0.5 * (b * np.cos(angle) + a * np.sin(angle)), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[..., 0, :], 0.5 * x[..., 0, :], rtol=1e-6)  # position 0: no turn


def turned(x, theta, scale=1.0):
    """The rotary embedding in the rotate-half pairing, a pair as one complex
    number turned by its angle: float64, on the host."""
    x = np.asarray(x, np.float64)
    t, size = x.shape[-2:]
    angle = np.arange(t)[:, None] * theta ** (-2.0 * np.arange(size // 2) / size)
    z = (x[..., :size // 2] + 1j * x[..., size // 2:]) * np.exp(1j * angle) * scale
    return np.concatenate([z.real, z.imag], axis=-1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rotate_half_turns_each_pair_as_a_complex_number_and_answers_in_float32(dtype):
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 40, 16)).astype(dtype)
    got = compiled(lambda x: rotate_half(x, 1e6, 0.25), x)
    assert got.dtype == jnp.float32 and rel_err(got, turned(x, 1e6, 0.25)) < 1e-5
    # a turn keeps every pair's length: the scale is all that changes a head's norm
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               0.25 * np.linalg.norm(np.asarray(x, np.float32), axis=-1), rtol=1e-5)


def test_rotary_turns_the_tables_columns_and_passes_the_others_through_unrotated_and_unscaled():
    """Tables of three pairs on a head of ten columns: columns 0 to 5 turn (``i``
    with ``i + 3``) with ``cos`` and ``sin`` times the factor, columns 6 to 9 pass
    through with the scale alone; and ``rotate_half`` is the tables ``theta ** (-2i /
    size)`` over all columns, bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 2, 12, 10))
    inv_freq = jnp.asarray([1.0, 0.3, 0.01])
    got = compiled(lambda x: rotary(x, inv_freq, 0.5, 1.4), x)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    angle = np.arange(12)[:, None] * np.asarray(inv_freq, np.float64)
    z = (np.asarray(x[..., :3], np.float64) + 1j * np.asarray(x[..., 3:6], np.float64)) * np.exp(
        1j * angle) * 0.5 * 1.4
    assert rel_err(got[..., :6], np.concatenate([z.real, z.imag], axis=-1)) < 1e-6
    np.testing.assert_array_equal(got[..., 6:], 0.5 * x[..., 6:])  # no turn, no factor
    np.testing.assert_array_equal(got[..., 0, :6], np.float32(0.5 * 1.4) * x[..., 0, :6])
    size, theta = 8, 1e4
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 6, size))
    tables = 1.0 / (theta ** (jnp.arange(0, size, 2, dtype=jnp.float32) / size))
    np.testing.assert_array_equal(compiled(lambda x: rotate_half(x, theta, 0.25), x),
                                  compiled(lambda x: rotary(x, tables, 0.25), x))
    assert RotaryTables((1.0, 0.3, 0.01), 1.4).columns == 6 and RotaryTables((1.0,)).factor == 1.0


@pytest.mark.parametrize("by", [0, 1, 3, -2, 9, -9])
def test_shift_moves_positions_later_or_earlier_and_zeros_move_in(by):
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 4))
    got = compiled(lambda x: shift(x, by), x)
    want = np.zeros(x.shape, np.float32)
    if by >= 0:
        want[:, by:] = np.asarray(x)[:, :9 - by]
    else:
        want[:, :by] = np.asarray(x)[:, -by:]
    np.testing.assert_array_equal(got, want)
    # along another axis, and its transpose is the shift the other way
    np.testing.assert_array_equal(compiled(lambda x: shift(x, by, axis=2), x.swapaxes(1, 2)),
                                  want.swapaxes(1, 2))
    back = compiled(lambda x, g: jax.vjp(lambda v: shift(v, by), x)[1](g)[0], x, x)
    np.testing.assert_array_equal(back, compiled(lambda x: shift(x, -by), x))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_swiglu_is_the_gated_unit_of_three_float32_kernels(dtype):
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 6, 16))
    layer = SwiGLU(24, dtype)
    params = layer.init(jax.random.PRNGKey(9), x)["params"]
    assert jax.tree.map(lambda p: (p.shape, p.dtype), params) == {
        name: (shape, jnp.float32) for name, shape in
        (("gate", (16, 24)), ("up", (16, 24)), ("down", (24, 16)))}
    # normal(0, 0.02): large enough here to read the gate's curve
    params = jax.tree.map(lambda p: 25.0 * p, params)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda params, x: layer.apply({"params": params}, x), params, x)
        want = compiled(lambda p, x: (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"],
                        params, x)
    assert got.dtype == dtype and rel_err(got, want) < (1e-6 if dtype == jnp.float32 else 2e-2)


class _Logits:
    """A model of ids to logits: a table's rows."""

    def apply(self, variables, ids):
        return variables["params"]["table"][ids]


def test_the_next_token_loss_is_the_mean_over_each_sequences_targets():
    vocab = 37
    ids = jax.random.randint(jax.random.PRNGKey(10), (3, 12), 0, vocab)
    params = {"table": jax.random.normal(jax.random.PRNGKey(11), (vocab, vocab))}

    def sliced(params, ids):  # the last position has no target and is cut off before the mean
        logp = jax.nn.log_softmax(params["table"][ids[:, :-1]])
        return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))

    got, got_grad = compiled(jax.value_and_grad(next_token_loss_fn(_Logits())), params, ids)
    want, want_grad = compiled(jax.value_and_grad(sliced), params, ids)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert rel_err(got_grad["table"], want_grad["table"]) < 1e-6
    # a sequence's last token is nobody's input: its row takes no gradient unless drawn elsewhere
    last_only = np.setdiff1d(np.asarray(ids[:, -1]), np.asarray(ids[:, :-1]))
    assert not np.asarray(got_grad["table"])[last_only].any()


# -- the attention layer ---------------------------------------------------------

#: tables over half of a head's eight columns, blended frequencies, a factor
TABLES = RotaryTables((1.0, 0.05), 1.4)
#: the layer as each model builds it: ``norm_eps``, ``rope_theta``, ``window``, ``rope``, ``gate``
SETTINGS = {
    "normed_heads_and_rotary": dict(norm_eps=1e-5, rope_theta=1e6),   # lfm2_moe
    "rotary": dict(rope_theta=1e6),                                  # ouro, smallthinker's windowed
    "no_positions": dict(),                                          # smallthinker's global layers
    "rotary_under_a_window": dict(rope_theta=1.5e6, window=5),       # smallthinker's windowed
    "no_positions_under_a_window": dict(window=5),                   # the two keys apart
    "gated_tables_on_half_the_columns": dict(rope=TABLES, gate=True),  # laguna's global layers
    "gated_rotary_under_a_window": dict(rope_theta=1e4, window=5, gate=True),  # its windowed
}
HEADS, KV_HEADS, SIZE, HIDDEN = 6, 2, 8, 32


def plain_layer(params, x, norm_eps=None, rope_theta=None, window=None, rope=None, gate=False):
    """The layer written down: three projections onto heads, a norm over each
    head's columns if any, the rotation if any (all columns at ``rope_theta``,
    or the tables' columns with their factor, the others passing through), every
    score under the mask, a scalar a head and position from the layer's input on
    the result if gated, and the output projection over ``(heads, head size)``."""
    def heads_of(name, count):
        y = jnp.einsum("btm,mhd->bhtd", x, params[name + "_proj"].reshape(HIDDEN, count, SIZE))
        if norm_eps is not None and name != "v":
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + norm_eps)
            y = y * params[name + "_norm"]["scale"]
        if rope_theta is not None and name != "v":
            t = y.shape[2]
            angle = jnp.arange(t)[:, None] * rope_theta ** (-2.0 * jnp.arange(SIZE // 2) / SIZE)
            a, b = y[..., :SIZE // 2], y[..., SIZE // 2:]
            y = jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                 b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)
        if rope is not None and name != "v":
            half = len(rope.inv_freq)
            angle = jnp.arange(y.shape[2])[:, None] * jnp.asarray(rope.inv_freq)
            cos, sin = rope.factor * jnp.cos(angle), rope.factor * jnp.sin(angle)
            a, b = y[..., :half], y[..., half:2 * half]
            y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, y[..., 2 * half:]], axis=-1)
        return y

    ctx = quadratic_attention(heads_of("q", HEADS), heads_of("k", KV_HEADS), heads_of("v", KV_HEADS),
                              1.0 / math.sqrt(SIZE), window)
    if gate:
        ctx = ctx * jax.nn.sigmoid(jnp.einsum("btm,mh->bht", x, params["gate_proj"]))[..., None]
    return jnp.einsum("bhtd,hdm->btm", ctx, params["out_proj"].reshape(HEADS, SIZE, HIDDEN))


def drawn_layer(setting, cls=GroupedQueryAttention, dtype=jnp.float32):
    layer = cls(HEADS, KV_HEADS, SIZE, dtype, **SETTINGS[setting])
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 16, HIDDEN))
    params = layer.init(jax.random.PRNGKey(13), x)["params"]
    # normal(0, 0.02) leaves every score near zero: kernels of a size that tells the keys apart,
    # and head norms' scales that are not all one
    params = jax.tree.map(lambda p: 12.0 * p if p.ndim == 2 else p + 0.1 * jnp.arange(SIZE), params)
    return layer, params, x


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_the_attention_layer_equals_quadratic_attention_on_its_own_projections(setting):
    layer, params, x = drawn_layer(setting)
    probe = jax.random.normal(jax.random.PRNGKey(14), x.shape)

    def both_passes(fn):
        def run(params, x):
            out, pull = jax.vjp(fn, params, x)
            return (out,) + pull(probe)
        return run

    with jax.default_matmul_precision("highest"):
        got = compiled(both_passes(lambda params, x: layer.apply({"params": params}, x)), params, x)
        want = compiled(both_passes(lambda params, x: plain_layer(params, x, **SETTINGS[setting])),
                        params, x)
    assert got[0].dtype == x.dtype and rel_err(got[0], want[0]) < 1e-5
    assert jax.tree.structure(got[1]) == jax.tree.structure(want[1])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]), jax.tree.leaves(want[1])):
        assert np.linalg.norm(w) > 0 and rel_err(g, w) < 1e-5, jax.tree_util.keystr(path)
    assert rel_err(got[2], want[2]) < 1e-5  # and the input's
    # the mask and the positions are functions of their own
    with jax.default_matmul_precision("highest"):
        others = [compiled(lambda params, x: plain_layer(params, x, **SETTINGS[other]), params, x)
                  for other in SETTINGS
                  if "norm_eps" not in SETTINGS[other] and "norm_eps" not in SETTINGS[setting]
                  and SETTINGS[other].get("gate") == SETTINGS[setting].get("gate")  # one tree
                  and other != setting]
    assert all(rel_err(other, want[0]) > 0.01 for other in others)


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_the_attention_layers_parameters_are_four_kernels_and_the_head_norms_if_any(setting):
    _, params, _ = drawn_layer(setting)
    kernels = {"q_proj": (HIDDEN, HEADS * SIZE), "k_proj": (HIDDEN, KV_HEADS * SIZE),
               "v_proj": (HIDDEN, KV_HEADS * SIZE), "out_proj": (HEADS * SIZE, HIDDEN)}
    norms = {name: {"scale": (SIZE,)} for name in ("q_norm", "k_norm")
             } if "norm_eps" in SETTINGS[setting] else {}
    if SETTINGS[setting].get("gate"):  # one column a query head
        kernels["gate_proj"] = (HIDDEN, HEADS)
    assert jax.tree.map(lambda p: p.shape, params) == {**kernels, **norms}
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))


@pytest.mark.parametrize("setting", list(SETTINGS))
def test_the_attention_layer_rounds_each_operand_once_and_names_its_core(setting):
    """In bf16: ``q`` and ``k`` reach the kernel in the compute dtype from a
    float32 pass (no second rounding), and the kernel runs under the part a
    capture reads: ``attn_window_core`` under a window, ``attn_core`` else."""
    layer, params, x = drawn_layer(setting, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda params, x: layer.apply({"params": params}, x))(params, x)
    names = {str(eqn.source_info.name_stack) for eqn in jaxpr.eqns}
    core = "attn_window_core" if "window" in SETTINGS[setting] else "attn_core"
    other = "attn_core" if core == "attn_window_core" else "attn_window_core"
    assert any(f"part={core}" in name for name in names)
    assert not any(name.endswith(f"part={other}") or f"part={other}/" in name for name in names)
    assert any("part=attn_proj" in name for name in names)
    assert any("part=attn_gate" in name for name in names) == bool(SETTINGS[setting].get("gate"))
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda params, x: layer.apply({"params": params}, x), params, x)
        want = compiled(lambda params, x: plain_layer(params, x, **SETTINGS[setting]), params, x)
    assert got.dtype == jnp.bfloat16 and rel_err(got, want) < 3e-2


def test_tables_beside_a_theta_or_wider_than_a_head_are_refused():
    x = jnp.zeros((1, 4, HIDDEN))
    for kwargs in (dict(rope=TABLES, rope_theta=1e4), dict(rope=RotaryTables((1.0,) * 5))):
        with pytest.raises(ValueError, match="tables"):
            GroupedQueryAttention(HEADS, KV_HEADS, SIZE, jnp.float32, **kwargs).init(
                jax.random.PRNGKey(0), x)


def test_smallthinkers_layer_is_the_shared_one_with_the_kernel_looked_up_in_its_module(
        monkeypatch):
    """``tests/benchmark`` replaces ``smallthinker_moe.causal_attention``: the
    subclass overrides ``core`` alone, so that the name is looked up there."""
    cls = smallthinker_moe.WindowOrGlobalAttention
    assert issubclass(cls, GroupedQueryAttention)
    assert [name for name, value in vars(cls).items()
            if inspect.isfunction(value) and not name.startswith("__")] == ["core"]
    layer, params, x = drawn_layer("rotary_under_a_window", cls)
    shared, _, _ = drawn_layer("rotary_under_a_window")

    def run(layer):
        return compiled(lambda params, x: layer.apply({"params": params}, x), params, x)

    np.testing.assert_array_equal(run(layer), run(shared))
    seen = []

    def no_window(q, k, v, scale, window=None):
        seen.append(window)
        return decoder.causal_attention(q, k, v, scale)

    monkeypatch.setattr(smallthinker_moe, "causal_attention", no_window)
    assert rel_err(run(layer), run(shared)) > 0.01 and seen == [5]


# -- the module's place among the model files ------------------------------------


def _imports(path):
    """``(module, name)`` of every ``from module import name`` in a file."""
    with open(path) as f:
        tree = ast.parse(f.read())
    return [(node.module, alias.name) for node in ast.walk(tree)
            if isinstance(node, ast.ImportFrom) for alias in node.names]


def test_no_model_file_imports_another_and_no_private_name_crosses_modules():
    models = os.path.join(REPO_ROOT, "bagua_tpu", "models")
    for model in MODELS:
        for module, name in _imports(os.path.join(models, model + ".py")):
            if module.startswith("bagua_tpu.models"):
                assert module in ("bagua_tpu.models.decoder", "bagua_tpu.models.embedding",
                                  "bagua_tpu.models.losses"), (model, module)
            assert not name.startswith("_") or not module.startswith("bagua_tpu"), (model, name)
    own = {module for module, _ in _imports(os.path.join(models, "decoder.py"))
           if module.startswith("bagua_tpu")}
    assert own == {"bagua_tpu.kernels.causal_attention", "bagua_tpu.models.losses",
                   "bagua_tpu.observability.annotations"}
    # the names the benchmark and its tests read are bound to the one definition
    from bagua_tpu.models import lfm2_moe, llama, nemotron_h, ouro
    assert (lfm2_moe.lfm2_moe_loss_fn is smallthinker_moe.smallthinker_loss_fn
            is nemotron_h.nemotron_h_loss_fn is next_token_loss_fn)
    assert ouro.RMSNorm is llama.RMSNorm is RMSNorm


def test_importing_a_decoder_model_loads_no_model_that_no_cell_runs():
    code = "\n".join([
        "import json, sys", "import bagua_tpu, bagua_tpu.trainer", "loaded = {}",
        f"for model in {MODELS!r}:",
        "    before = set(sys.modules)",
        "    __import__('bagua_tpu.models.' + model)",
        "    loaded[model] = sorted(m for m in set(sys.modules) - before if m.startswith('bagua_tpu'))",
        "print(json.dumps(loaded))"])
    done = subprocess.run([sys.executable, "-c", code], env=worker_env(JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    loaded = json.loads(done.stdout.strip().splitlines()[-1])
    assert "bagua_tpu.models.decoder" in loaded[MODELS[0]]
    for model in MODELS:
        assert f"bagua_tpu.models.{model}" in loaded[model]
        assert not set(loaded[model]) & set(NOT_A_DECODERS), (model, loaded[model])
        assert not {f"bagua_tpu.models.{other}" for other in MODELS if other != model} & set(
            loaded[model]), (model, loaded[model])
