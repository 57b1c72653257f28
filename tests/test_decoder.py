"""``models/decoder.py``: the parts the decoder models share, each against a
plain oracle, once and compiled (``helpers.compiled``): ``RMSNorm``,
``rotate_half`` against the rotation of each pair as a complex number,
``shift``, ``SwiGLU``, the next-token loss against a slice then a mean, and
the grouped-query attention layer at each of the settings a model builds it
with, value and every gradient leaf, against quadratic attention on the
layer's own projections; the pinned entry and exit passes
(``kernels/head_passes.py``) against ``rotary`` and the plain gate, and which
layers take them.  And the module's place among the model files: who imports
what, pinned in a subprocess."""

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

from bagua_tpu.kernels import head_passes
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_short_convolution_without_a_bias_is_the_one_with_a_bias_of_zero(dtype):
    """``causal_conv_silu`` serves ``nemotron_h.py`` with a bias (its arithmetic
    against a direct sum in ``test_nemotron_h.py``) and ``solar_open2.py``
    without: the same values and gradients as a bias of zero gives, the last tap
    on the current position, and ``xbc`` and the taps alone kept."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    xbc = jax.random.normal(keys[0], (2, 9, 5)).astype(dtype)
    taps = jax.random.normal(keys[1], (4, 5))
    probe = jax.random.normal(keys[2], (2, 9, 5))

    def both(*bias):
        def run(xbc, taps):
            out, pull = jax.vjp(lambda xbc, taps: decoder.causal_conv_silu(xbc, taps, *bias), xbc, taps)
            return (out,) + pull(probe.astype(out.dtype))
        return compiled(run, xbc, taps)

    without, with_zero = both(), both(jnp.zeros((5,)))
    for a, b in zip(without, with_zero):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    first = np.asarray(xbc[:, 0], np.float32) * np.asarray(taps[3])
    np.testing.assert_allclose(np.asarray(without[0][:, 0], np.float32), first / (1 + np.exp(-first)),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    _, residuals = jax.vjp(decoder.causal_conv_silu, xbc, taps)
    kept = sorted((x.shape, str(x.dtype)) for x in jax.tree.leaves(residuals))
    assert kept == sorted([((2, 9, 5), jnp.dtype(dtype).name), ((4, 5), "float32")])


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
    "gated_by_the_column_without_positions": dict(gate="column"),    # solar_open2's GQA layers
}
HEADS, KV_HEADS, SIZE, HIDDEN = 6, 2, 8, 32


def plain_layer(params, x, norm_eps=None, rope_theta=None, window=None, rope=None, gate=False,
                heads=HEADS, kv_heads=KV_HEADS, size=SIZE):
    """The layer written down: three projections onto heads, a norm over each
    head's columns if any, the rotation if any (all columns at ``rope_theta``,
    or the tables' columns with their factor, the others passing through), every
    score under the mask, a scalar a head and position from the layer's input on
    the result if gated, and the output projection over ``(heads, head size)``."""
    def heads_of(name, count):
        y = jnp.einsum("btm,mhd->bhtd", x, params[name + "_proj"].reshape(HIDDEN, count, size))
        if norm_eps is not None and name != "v":
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + norm_eps)
            y = y * params[name + "_norm"]["scale"]
        if rope_theta is not None and name != "v":
            t = y.shape[2]
            angle = jnp.arange(t)[:, None] * rope_theta ** (-2.0 * jnp.arange(size // 2) / size)
            a, b = y[..., :size // 2], y[..., size // 2:]
            y = jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                 b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)
        if rope is not None and name != "v":
            half = len(rope.inv_freq)
            angle = jnp.arange(y.shape[2])[:, None] * jnp.asarray(rope.inv_freq)
            cos, sin = rope.factor * jnp.cos(angle), rope.factor * jnp.sin(angle)
            a, b = y[..., :half], y[..., half:2 * half]
            y = jnp.concatenate([a * cos - b * sin, b * cos + a * sin, y[..., 2 * half:]], axis=-1)
        return y

    ctx = quadratic_attention(heads_of("q", heads), heads_of("k", kv_heads), heads_of("v", kv_heads),
                              1.0 / math.sqrt(size), window)
    if gate == "column":  # one value a head column and position
        ctx = ctx * jax.nn.sigmoid(jnp.einsum(
            "btm,mhd->bhtd", x, params["gate_proj"].reshape(HIDDEN, heads, size)))
    elif gate:  # a scalar a head and position
        ctx = ctx * jax.nn.sigmoid(jnp.einsum("btm,mh->bht", x, params["gate_proj"]))[..., None]
    return jnp.einsum("bhtd,hdm->btm", ctx, params["out_proj"].reshape(heads, size, HIDDEN))


def _both_passes(fn, probe):
    def run(*args):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(probe.astype(out.dtype))
    return run


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

    with jax.default_matmul_precision("highest"):
        got = compiled(_both_passes(lambda params, x: layer.apply({"params": params}, x), probe),
                       params, x)
        want = compiled(_both_passes(
            lambda params, x: plain_layer(params, x, **SETTINGS[setting]), probe), params, x)
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
    if SETTINGS[setting].get("gate"):  # one column a query head, or one a head column
        kernels["gate_proj"] = (HIDDEN, HEADS * (SIZE if SETTINGS[setting]["gate"] == "column" else 1))
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


# -- the pinned entry and exit passes (kernels/head_passes.py) ----------------------

#: what the entry turns: every column at ``rope_theta`` (laguna's windowed layers,
#: smallthinker's), tables on half of a head's 128 columns with a factor (laguna's global)
TURNS = {
    "rotary_128": (lambda size: decoder._inv_freq(1e4, size), 1.0),
    "tables_64_of_128_with_a_factor": (lambda size: jnp.asarray(
        [1e4 ** (-i / 32.0) * (0.3 if i > 20 else 1.0) for i in range(size // 4)], jnp.float32), 1.4),
}
PASS_T, PASS_SIZE = 32, 128


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [1, 8], ids=["1_head", "8_heads"])  # k's side, q's side
@pytest.mark.parametrize("what", [*TURNS, "gate"])
def test_the_pinned_passes_are_todays_rotation_and_gate_in_both_directions(what, heads, dtype):
    """The formulas (a rotation of the lanes against full-width tables; the
    gate from ``a (batch, positions, heads)``) against ``rotary`` and the
    multiplication the layer wrote until PR 50, *bit for bit* in both types,
    value and every gradient but the gate's ``d a`` (a sum over 128 columns in
    another order): eagerly, because XLA:CPU contracts ``a * b + c * d`` to
    fused multiply-adds by the shape of the expression it compiles.  And the
    Pallas kernels, through the interpreter, against the formulas: to float32
    rounding, the rounded values bit for bit; the entry's cotangent in the
    compute type's values where a product would round it so itself."""
    keys = jax.random.split(jax.random.PRNGKey(20), 3)
    shape = (2, heads, PASS_T, PASS_SIZE)
    probe = jax.random.normal(keys[0], shape)
    if what == "gate":
        args = (jax.random.normal(keys[1], shape).astype(dtype),
                jax.nn.sigmoid(jax.random.normal(keys[2], (2, PASS_T, heads))))
        today = lambda ctx, a: (ctx * a.swapaxes(1, 2)[..., None]).astype(dtype)
        plain, kernels = head_passes._gated, lambda ctx, a: head_passes._gate_kernels(ctx, a, True)
    else:
        make, factor = TURNS[what]
        inv_freq, scale = make(PASS_SIZE), 1.0 / math.sqrt(PASS_SIZE)
        args = (3.0 * jax.random.normal(keys[1], shape),)
        today = lambda y: rotary(y, inv_freq, scale, factor).astype(dtype)

        def tabled(turn):
            return lambda y: turn(y, *head_passes.rotary_tables(
                inv_freq, PASS_T, PASS_SIZE, scale, factor))
        plain = tabled(lambda y, tables, shifts: head_passes._turned(y, tables, shifts, dtype))
        kernels = tabled(lambda y, tables, shifts: head_passes._turn_kernels(
            y, tables, shifts, jnp.dtype(dtype), True))
    want = _both_passes(today, probe)(*args)  # eager: see above
    got = _both_passes(plain, probe)(*args)
    assert got[0].dtype == dtype and len(got) == len(want) == 1 + len(args)
    for n, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        if what == "gate" and n == 2:
            assert rel_err(g, w) < 1e-6
        else:
            np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
    formulas = compiled(_both_passes(plain, probe), *args)
    with jax.default_matmul_precision("highest"):  # read when the backward rule is traced
        through = compiled(_both_passes(kernels, probe), *args)
    np.testing.assert_array_equal(np.asarray(through[0], np.float32), np.asarray(formulas[0], np.float32))
    for g, w in zip(through[1:], formulas[1:]):
        assert g.dtype == w.dtype and rel_err(g, w) < 1e-6
    if what != "gate":
        # at the default precision the products that read the entry's cotangent round it to the
        # compute type themselves: the kernel writes it so rounded, still as float32's cotangent
        rounded = compiled(_both_passes(kernels, probe), *args)[1]
        assert rounded.dtype == jnp.float32
        np.testing.assert_array_equal(rounded, rounded.astype(dtype).astype(jnp.float32))
        assert rel_err(rounded, formulas[1]) < (1e-6 if dtype == jnp.float32 else 4e-3)


def test_the_tables_are_rotarys_over_the_whole_head_and_a_shape_the_kernels_refuse_takes_the_formulas():
    inv_freq = jnp.asarray([1.0, 0.1, 0.01])
    tables, shifts = head_passes.rotary_tables(inv_freq, 5, 8, 0.5, 1.4)
    angle = np.arange(5)[:, None] * np.asarray(inv_freq)
    cos, sin = 0.7 * np.cos(angle), 0.7 * np.sin(angle)
    zeros, rest = np.zeros((5, 3)), np.zeros((5, 2))
    assert shifts == (5, 3) and tables.shape == (3, 5, 8) and tables.dtype == jnp.float32
    np.testing.assert_allclose(tables[0], np.concatenate([cos, cos, rest + 0.5], -1), rtol=1e-6)
    np.testing.assert_allclose(tables[1], np.concatenate([-sin, zeros, rest], -1), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(tables[2], np.concatenate([zeros, sin, rest], -1), rtol=1e-6, atol=1e-7)
    whole, shifts = head_passes.rotary_tables(jnp.asarray([1.0, 0.1]), 5, 4)
    assert shifts == (2,) and whole.shape == (2, 5, 4)  # either way round: one rotation
    # 128 lanes and blocks of 16 positions or the formulas; float32 in, whatever the backend
    y = jnp.ones((1, 2, 24, 128))
    assert head_passes._turn_rows(y) is None and head_passes._gate_rows(y) is None
    assert head_passes._turn_rows(jnp.ones((1, 2, 32, 64))) is None
    assert head_passes._turn_rows(jnp.ones((1, 28, 8192, 128))) == 512  # 7 heads a block
    assert head_passes._gate_rows(jnp.ones((1, 64, 8192, 128), jnp.bfloat16)) == 128
    with pytest.raises(ValueError, match="float32"):
        head_passes.turn_heads(y.astype(jnp.bfloat16), tables, shifts, jnp.bfloat16)
    with pytest.raises(ValueError, match="float32"):
        head_passes.gate_heads(y, jnp.ones((1, 24, 2), jnp.bfloat16))


#: ``(heads, kv_heads, head size)`` and the layer's settings: which models build it so, and
#: the calls the traced layer holds on a TPU: entry kernels, exit kernels, layout constraints
PINNED = {
    "laguna_windowed": ((16, 2, 128), dict(rope_theta=1e4, window=5, gate=True), (2, 1, 0)),
    "laguna_global": ((12, 2, 128), dict(rope=RotaryTables(tuple(0.5 ** i for i in range(32)), 1.4),
                                         gate=True), (2, 1, 0)),
    "smallthinker_windowed": ((14, 2, 128), dict(rope_theta=1.5e6, window=5), (2, 0, 1)),
    "smallthinker_global": ((14, 2, 128), dict(), (0, 0, 2)),  # q's product and ctx held, no pass
    "grouped_at_256": ((4, 2, 256), dict(rope_theta=1e4), (2, 0, 1)),
    "ouro": ((4, 4, 128), dict(rope_theta=1e6), (0, 0, 0)),  # a key-value head a query head
    "lfm2": ((8, 2, 64), dict(norm_eps=1e-5, rope_theta=1e6), (0, 0, 0)),  # half a lane tile
    "gated_heads_of_64": ((8, 2, 64), dict(rope_theta=1e4, gate=True), (0, 0, 0)),
    # no positions, the gate a value a column: q's product and the gated ctx held, no pass
    "solar_open2": ((8, 1, 128), dict(gate="column"), (0, 0, 2)),
}


@pytest.mark.parametrize("case", list(PINNED))
def test_grouped_queries_at_whole_lane_tiles_take_the_pinned_passes_and_no_other_layer_does(
        case, monkeypatch):
    """The condition is the layer's own fields (``head_size % 128 == 0 and
    heads > kv_heads``); the calls are chosen by backend, and here the backend
    is the CPU: steered, in the test, for a trace that compiles nothing.  On
    the CPU the same layer is the formulas, and equals the layer written down
    whichever path it took."""
    (heads, kv_heads, size), settings, (turns, gates, constraints) = PINNED[case]
    layer = GroupedQueryAttention(heads, kv_heads, size, jnp.bfloat16, **settings)
    x = jax.random.normal(jax.random.PRNGKey(21), (1, 32, HIDDEN))
    params = layer.init(jax.random.PRNGKey(22), x)["params"]
    params = jax.tree.map(lambda p: 6.0 * p if p.ndim == 2 else p, params)

    def calls():
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda params, x: jnp.sum(layer.apply({"params": params}, x).astype(jnp.float32))))(params, x)
        found = {"head_turn": 0, "head_turn_backward": 0, "head_gate": 0, "head_gate_backward": 0,
                 "layout_constraint": 0}

        def walk(jaxpr, outer=""):
            for eqn in jaxpr.eqns:
                scopes = f"{outer}/{eqn.source_info.name_stack}"
                if eqn.primitive.name == "layout_constraint":
                    found["layout_constraint"] += 1
                name = eqn.params.get("name")
                if eqn.primitive.name == "pallas_call" and name in found:
                    found[name] += 1  # under its part in both directions
                    assert f"part={'attn_gate' if 'gate' in name else 'attn_proj'}" in scopes, scopes
                    continue
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, scopes)

        walk(jaxpr.jaxpr)
        return found

    assert not any(calls().values())  # the CPU: the formulas
    with monkeypatch.context() as steered:
        steered.setattr(jax, "default_backend", lambda: "tpu")
        found = calls()
    assert found == {"head_turn": turns, "head_turn_backward": turns, "head_gate": gates,
                     "head_gate_backward": gates, "layout_constraint": 2 * constraints}, found
    shape = dict(heads=heads, kv_heads=kv_heads, size=size)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda params, x: layer.apply({"params": params}, x), params, x)
        want = compiled(lambda params, x: plain_layer(params, x, **settings, **shape), params, x)
    assert got.dtype == jnp.bfloat16 and rel_err(got, want) < 3e-2


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
    assert own == {"bagua_tpu.kernels.causal_attention", "bagua_tpu.kernels.head_passes",
                   "bagua_tpu.models.losses",
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
