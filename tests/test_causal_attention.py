"""``kernels/causal_attention.py`` on the CPU, in one place: the blocked
composition against quadratic attention in value and gradient (one head count,
key-value heads that serve a group, a window), the call without key-value
heads and without a window unchanged bit for bit, the chip's kernels through
Pallas' interpreter at each of the shapes a model calls them with, and which
kernel a shape goes to.  A model's tests do not prove the composition again:
they match the whole model against its reference.

Every comparison runs both sides compiled (``helpers.compiled``); the two
``eager`` cases of the bit-for-bit tests are eager because eager dispatch is
what they pin."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.kernels import causal_attention as causal_attention_module
from bagua_tpu.kernels.causal_attention import (
    SPLASH_BLOCKS,
    SPLASH_NARROW_BLOCKS,
    blocked_causal_attention,
    causal_attention,
    splash_blocks,
)
from helpers import compiled
from oracles import both_passes, quadratic_attention, rel_err


def _splash(scale, window=None):
    """The TPU branch of ``causal_attention`` through Pallas' interpreter."""
    return lambda q, k, v: causal_attention_module._splash_causal_attention(
        q, k, v, scale, interpret=True, window=window)


def _f32(x):
    return x.astype(jnp.float32)


# -- the composition against quadratic attention -------------------------------


@pytest.mark.parametrize("block_q", [8, 16, 64])
def test_blocked_attention_equals_quadratic_attention_in_value_and_gradient(block_q):
    # a head of 12 dimensions without position and 4 rotary ones, as GLM's toy model's
    b, h, t, nope, rope = 2, 3, 64, 12, 4
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    q_nope, k_nope = (jax.random.normal(kk, (b, h, t, nope)) for kk in keys[:2])
    q_rope = jax.random.normal(keys[2], (b, h, t, rope))
    k_rope = jnp.broadcast_to(jax.random.normal(keys[3], (b, 1, t, rope)), (b, h, t, rope))
    v = jax.random.normal(keys[4], (b, h, t, nope + rope))
    q, k = jnp.concatenate([q_nope, q_rope], -1), jnp.concatenate([k_nope, k_rope], -1)
    scale = 1 / math.sqrt(nope + rope)

    def value_and_gradients(attn):
        def run(q, k, v):
            return attn(q, k, v), jax.grad(
                lambda *a: jnp.sum(jnp.cos(attn(*a))), argnums=(0, 1, 2))(q, k, v)
        return run

    with jax.default_matmul_precision("highest"):
        got, got_grads = compiled(value_and_gradients(
            lambda *a: blocked_causal_attention(*a, scale, block_q)), q, k, v)
        want, want_grads = compiled(value_and_gradients(
            lambda *a: quadratic_attention(*a, scale)), q, k, v)
        # off the chip the one entry point is the composition
        entry = compiled(lambda *a: causal_attention(*a, scale), q, k, v)
        whole = got if block_q == 64 else compiled(
            lambda *a: blocked_causal_attention(*a, scale, 64), q, k, v)
    assert rel_err(got, want) < 1e-6
    for g, w in zip(got_grads, want_grads):
        assert rel_err(g, w) < 1e-5
    np.testing.assert_array_equal(entry, whole)
    with pytest.raises(ValueError, match="do not divide"):
        blocked_causal_attention(q, k, v, scale, 48)


@pytest.mark.parametrize("block_q", [8, 32, 64])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1), (6, 6)],
                         ids=["4_a_kv_head", "one_kv_head", "one_each"])
def test_blocked_grouped_attention_equals_quadratic_attention_with_repeated_keys(
        heads, kv_heads, block_q):
    b, t, d, scale = 2, 64, 16, 0.25
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d)) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d)) for kk in keys[2:])
    args = (q, k, v, d_out)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: both_passes(
            lambda *qkv: blocked_causal_attention(*qkv, scale, block_q), *a), *args)
        want = compiled(lambda *a: both_passes(
            lambda *qkv: quadratic_attention(*qkv, scale), *a), *args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel_err(g, w) < 1e-5
    if block_q == 64:  # off the chip the one entry point is the composition
        np.testing.assert_array_equal(
            compiled(lambda *a: causal_attention(*a, scale), q, k, v),
            compiled(lambda *a: blocked_causal_attention(*a, scale, 64), q, k, v))
    with pytest.raises(ValueError, match="heads divide"):
        causal_attention(q, k[:, :1].repeat(5, axis=1), v[:, :1].repeat(5, axis=1), scale)


@pytest.mark.parametrize("block_q", [8, 32, 64])
@pytest.mark.parametrize("window", [1, 5, 24, 33, 64, 100],
                         ids=lambda w: f"window{w}")
def test_the_compositions_window_equals_the_dense_mask_at_seven_queries_a_key(window, block_q):
    """Windows inside one block of queries, across blocks, of the whole
    sequence (64) and beyond it; 14 query heads on 2 key-value heads."""
    b, heads, kv_heads, t, d, scale = 2, 14, 2, 64, 16, 0.25
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d)) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d)) for kk in keys[2:])
    args = (q, k, v, d_out)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: both_passes(
            lambda *qkv: blocked_causal_attention(*qkv, scale, block_q, window), *a), *args)
        want = compiled(lambda *a: both_passes(
            lambda *qkv: quadratic_attention(*qkv, scale, window), *a), *args)
        causal = compiled(lambda *a: both_passes(
            lambda *qkv: quadratic_attention(*qkv, scale), *a), *args)
    for g, w in zip(got, want):
        # a window of one key is a softmax of one score: dq and dk are zero
        assert g.shape == w.shape and np.linalg.norm(g - w) < 1e-5 * max(
            np.linalg.norm(w), np.linalg.norm(d_out))
    # the window is a mask of its own until it holds the sequence
    assert (rel_err(want[0], causal[0]) > 0.05) == (window < t)
    if block_q == 64:  # off the chip the one entry point is the composition
        np.testing.assert_array_equal(
            compiled(lambda *a: causal_attention(*a, scale, window=window), q, k, v),
            compiled(lambda *a: blocked_causal_attention(
                *a, scale, 64, window if window < t else None), q, k, v))


def test_a_window_leaves_the_keys_behind_it_out_of_the_blocks():
    """The composition forms no score behind the window: the widest block of
    scores is ``window + block_q - 1`` keys, not the sequence."""
    q = jax.ShapeDtypeStruct((1, 7, 256, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1, 256, 16), jnp.float32)

    def widest(window):
        text = str(jax.make_jaxpr(
            lambda q, k, v: blocked_causal_attention(q, k, v, 1.0, 32, window))(q, kv, kv))
        return max(int(shape.split(",")[3]) for shape in
                   re.findall(r"f32\[(1,1,224,\d+)\]", text))

    assert widest(None) == 256 and widest(64) == 64 + 31 and widest(32) == 32 + 31
    with pytest.raises(ValueError, match="not even the current position"):
        causal_attention(jnp.zeros(q.shape), jnp.zeros(kv.shape), jnp.zeros(kv.shape), 1.0, window=0)


# -- what the composition was before key-value heads and before a window -------


def _one_head_count_composition(q, k, v, d_out, scale, block_q):
    """The composition as it was before key-value heads (PR 29), forward and
    backward, for the comparison bit for bit."""
    f32 = jnp.float32

    def scores(q_blk, k_seen, start):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_seen, preferred_element_type=f32) * scale
        rows = start + jnp.arange(q_blk.shape[2])[:, None]
        return jnp.where(jnp.arange(k_seen.shape[2])[None, :] <= rows, s, -1e30)

    blocks = [(i * block_q, (i + 1) * block_q) for i in range(q.shape[2] // block_q)]
    outs, lses = [], []
    for start, end in blocks:
        s = scores(q[:, :, start:end], k[:, :, :end], start)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :end],
                       preferred_element_type=f32) / l
        outs.append(o.astype(q.dtype))
        lses.append((m + jnp.log(l))[..., 0])
    out, lse = jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1, keepdims=True)
    dq, dk, dv = [], jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    for start, end in blocks:
        q_blk, do_blk = q[:, :, start:end], d_out[:, :, start:end]
        p = jnp.exp(scores(q_blk, k[:, :, :end], start) - lse[:, :, start:end, None])
        dv = dv.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(v.dtype), do_blk, preferred_element_type=f32))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, v[:, :, :end], preferred_element_type=f32)
        ds = (p * (dp - delta[:, :, start:end]) * scale).astype(q.dtype)
        dq.append(jnp.einsum("bhqk,bhkd->bhqd", ds, k[:, :, :end],
                             preferred_element_type=f32).astype(q.dtype))
        dk = dk.at[:, :, :end].add(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_blk, preferred_element_type=f32))
    return out, jnp.concatenate(dq, axis=2), dk.astype(k.dtype), dv.astype(v.dtype)


def _bit_for_bit_case(dtype):
    b, h, t, d, scale, block_q = 2, 3, 128, 32, 0.17, 32
    q, k, v, d_out = (jax.random.normal(kk, (b, h, t, d), dtype)
                      for kk in jax.random.split(jax.random.PRNGKey(0), 4))

    def now(q, k, v, d_out):
        return both_passes(lambda *a: blocked_causal_attention(*a, scale, block_q), q, k, v, d_out)

    def spelled(q, k, v, d_out):
        return both_passes(lambda *a: blocked_causal_attention(*a, scale, block_q, None),
                           q, k, v, d_out)

    def before(q, k, v, d_out):
        return _one_head_count_composition(q, k, v, d_out, scale, block_q)

    return (q, k, v, d_out), scale, t, now, spelled, before


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_call_with_one_head_count_is_unchanged_bit_for_bit(dtype, jitted):
    """``models/glm_moe.py`` calls with as many key-value heads as query
    heads: its result and its three gradients are the bits they were."""
    args, _, _, now, _, before = _bit_for_bit_case(dtype)
    if jitted:
        now, before = jax.jit(now), jax.jit(before)
    for got, want in zip(now(*args), before(*args)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_no_window_is_unchanged_bit_for_bit(dtype, jitted):
    """``models/glm_moe.py`` and ``models/lfm2_moe.py`` call without a
    window: result and gradients are the bits the composition gave before it
    had one (PR 29's text, above), and a window that holds the sequence is
    that call."""
    args, scale, t, now, spelled, before = _bit_for_bit_case(dtype)
    q, k, v, _ = args
    if jitted:
        now, spelled, before = jax.jit(now), jax.jit(spelled), jax.jit(before)
    for got, same, want in zip(now(*args), spelled(*args), before(*args)):
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(same, want)
    # the entry point: no window, None, and a window of the whole sequence trace to one program
    texts = {str(jax.make_jaxpr(lambda q, k, v: causal_attention(q, k, v, scale, **kw))(q, k, v))
             for kw in ({}, {"window": None}, {"window": t}, {"window": t + 5})}
    assert len(texts) == 1
    assert str(jax.make_jaxpr(lambda q, k, v: causal_attention(q, k, v, scale, window=t - 1))(
        q, k, v)) not in texts


# -- the chip's kernels through Pallas' interpreter -----------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("scale", [0.125, 0.11], ids=["scale_2^-3", "scale_0.11"])
@pytest.mark.parametrize("b,h,tiles,d", [(2, 2, 2, 128), (1, 1, 4, 256)],
                         ids=["2x2_two_tiles_d128", "1x1_four_tiles_d256"])
def test_the_chips_attention_kernels_equal_quadratic_attention_in_interpret_mode(
        b, h, tiles, d, scale, dtype):
    """The TPU branch of ``causal_attention`` through Pallas' interpreter, at
    the committed tile edges: blocks above the diagonal skipped, blocks on it
    masked, blocks below it whole, and more than one partial of ``dQ``.  What
    it computes is ``softmax((q * scale) k^T) v`` with ``q * scale`` rounded
    to ``q``'s type (the docstring says so): that function in float32 is the
    oracle, and beside it the stated function, which a scale that is no power
    of two meets one bf16 rounding of ``q`` further off."""
    t = tiles * causal_attention_module.SPLASH_BLOCK_MAJOR
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    args = tuple(jax.random.normal(kk, (b, h, t, d), dtype) for kk in keys)
    q = args[0]

    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: both_passes(_splash(scale), *a), *args)
        computed = compiled(lambda *a: both_passes(lambda q, k, v: quadratic_attention(
            _f32((q * scale).astype(q.dtype)), _f32(k), _f32(v), 1.0), *a), *args)
        stated = compiled(lambda *a: both_passes(lambda q, k, v: quadratic_attention(
            _f32(q), _f32(k), _f32(v), scale), *a), *args)
    assert all(g.dtype == dtype and g.shape == q.shape for g in got)
    # bf16: the probabilities and dS are rounded to the operands' type before each product
    near, one_more_rounding = (1e-5, 1e-5) if dtype == jnp.float32 else (6e-3, 1e-2)
    for g, c, s in zip(got, computed, stated):
        assert rel_err(g, c) < near
        assert rel_err(g, s) < (near if scale == 0.125 else one_more_rounding)
    if scale == 0.125:  # a power of two: the two functions are one
        for c, s in zip(computed, stated):
            np.testing.assert_array_equal(c, s)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_the_chips_kernels_serve_four_query_heads_a_key_value_head_in_interpret_mode(dtype):
    """The TPU branch at a head of 64 through Pallas' interpreter: 8 query
    heads on 2 key-value heads, two tiles of positions, no key repeated;
    ``dK`` and ``dV`` are sums over each group inside the kernel."""
    b, heads, kv_heads, d = 1, 8, 2, 64
    t = 2 * causal_attention_module.SPLASH_BLOCK_MAJOR
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d), dtype) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d), dtype) for kk in keys[2:])
    args = (q, k, v, d_out)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: both_passes(_splash(0.125), *a), *args)
        want = compiled(lambda *a: both_passes(lambda q, k, v: quadratic_attention(
            _f32(q), _f32(k), _f32(v), 0.125), *a), *args)
    near = 1e-5 if dtype == jnp.float32 else 6e-3
    for g, w, like in zip(got, want, (q, q, k, v)):
        assert g.dtype == dtype and g.shape == like.shape
        assert rel_err(g, w) < near


@pytest.mark.parametrize("window", [600, 1024, 1500], ids=lambda w: f"window{w}")
def test_the_chips_kernels_under_a_window_serve_seven_query_heads_in_interpret_mode(window):
    """The TPU branch through Pallas' interpreter at two tiles of positions: a
    window inside one tile, of one tile, and across the two; 7 query heads on
    one key-value head, no key repeated."""
    b, heads, kv_heads, d = 1, 7, 1, 32
    t = 2 * causal_attention_module.SPLASH_BLOCK_MAJOR
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, d_out = (jax.random.normal(kk, (b, heads, t, d), jnp.float32) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, kv_heads, t, d), jnp.float32) for kk in keys[2:])
    args = (q, k, v, d_out)
    with jax.default_matmul_precision("highest"):
        got = compiled(lambda *a: both_passes(_splash(0.125, window), *a), *args)
        want = compiled(lambda *a: both_passes(
            lambda *qkv: quadratic_attention(*qkv, 0.125, window), *a), *args)
    for g, w, like in zip(got, want, (q, q, k, v)):
        assert g.shape == like.shape and rel_err(g, w) < 1e-5


# -- which kernel a shape goes to -----------------------------------------------


def test_the_chips_attention_kernels_are_built_once_per_heads_and_positions():
    """Five layers of one model share one kernel object: the mask's block
    tables are numpy work on the host at trace time."""
    build = causal_attention_module._splash_kernel
    major = causal_attention_module.SPLASH_BLOCK_MAJOR
    assert build(2, 2 * major, True) is build(2, 2 * major, True)
    assert build(2, 2 * major, True) is not build(1, 2 * major, True)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        causal_attention_module._splash_causal_attention(q, q, q, 0.125, interpret=True).astype(
            jnp.float32))))(jax.ShapeDtypeStruct((1, 2, 2 * major, 128), jnp.bfloat16)))
    # one forward kernel and one backward kernel that gives dq, dk and dv
    assert text.count("pallas_call") == 2
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text and "splash_mha_dq" not in text


def test_a_group_goes_to_the_multi_query_kernels_and_one_head_count_to_what_it_had():
    build = causal_attention_module._splash_kernel
    major = causal_attention_module.SPLASH_BLOCK_MAJOR
    assert SPLASH_BLOCKS == dict(block_q=1024, block_kv=1024, block_kv_compute=256,
                                 block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512)
    grouped = build(4, major, True, multi_query=True)
    assert grouped is build(4, major, True, multi_query=True) and grouped is not build(4, major, True)
    assert grouped.kwargs["is_mqa"] and not build(4, major, True).kwargs["is_mqa"]

    def kernels(q_heads, kv_heads):
        q = jax.ShapeDtypeStruct((1, q_heads, major, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, kv_heads, major, 64), jnp.bfloat16)
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            causal_attention_module._splash_causal_attention(q, k, v, 1.0, interpret=True).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, kv, kv))

    # one forward and one fused backward kernel either way; no key is repeated
    for text, name in ((kernels(8, 2), "splash_mqa"), (kernels(2, 2), "splash_mha")):
        assert text.count("pallas_call") == 2 and name + "_fwd" in text and name + "_dkv" in text
    assert "splash_mha" not in kernels(8, 2) and "splash_mqa" not in kernels(2, 2)


def test_a_window_is_a_kernel_of_its_own_and_no_window_the_one_there_was():
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    build = causal_attention_module._splash_kernel
    major = causal_attention_module.SPLASH_BLOCK_MAJOR
    t = 8 * major
    causal = build(7, t, True, multi_query=True, window=None)
    assert causal is build(7, t, True, multi_query=True, window=None)
    windowed = build(7, t, True, multi_query=True, window=4 * major)
    assert windowed is not causal and windowed is build(7, t, True, multi_query=True, window=4 * major)
    assert windowed.kwargs["is_mqa"] and causal.kwargs["is_mqa"]
    # the published shapes: 8,192 positions in tiles of 1,024, a window of 4,096 keys.  The
    # causal mask keeps 36 of the 64 tiles; the window takes the 6 farthest from the diagonal
    assert (t, 4 * major) == (8192, 4096)

    def tiles(mask):
        blocks = np.asarray(mask[:, :]).reshape(8, major, 8, major)
        return int(blocks.any(axis=(1, 3)).sum())

    assert tiles(masks.CausalMask((t, t))) == 36
    local = masks.LocalMask((t, t), window_size=(4 * major - 1, 0), offset=0)
    assert tiles(local) == 30
    # and the mask is the issue's: i >= j and i - j < 4096
    i, j = np.arange(5000, 5003)[:, None], np.arange(t)[None, :]
    np.testing.assert_array_equal(np.asarray(local[5000:5003, :]), (i >= j) & (i - j < 4096))


def test_the_tile_edges_are_a_function_of_the_mask_and_todays_where_a_tile_is_mostly_work():
    """``splash_blocks(positions, window)``: the causal mask and SmallThinker's
    4,096 keys at 8,192 keep the edges they had and the fused backward; Laguna's
    512 keys take edges of 512 and a backward pass of two kernels."""
    today = dict(block_q=1024, block_kv=1024, block_kv_compute=256,
                 block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512)
    assert SPLASH_BLOCKS == today
    for window in (None, 4096, 1024, 8192):
        assert splash_blocks(8192, window) is SPLASH_BLOCKS
    narrow = splash_blocks(8192, 512)
    assert narrow is SPLASH_NARROW_BLOCKS is splash_blocks(8192, 1023) is splash_blocks(2048, 1)
    assert narrow == dict(block_q=512, block_kv=512, block_kv_compute=512, block_q_dkv=512,
                          block_kv_dkv=512, block_kv_dkv_compute=512, block_q_dq=512,
                          block_kv_dq=512, use_fused_bwd_kernel=False)
    edges = [v for k, v in narrow.items() if k.startswith("block")]
    assert all(causal_attention_module.SPLASH_BLOCK_MAJOR % e == 0 for e in edges)
    assert splash_blocks(8192 + 256, 512) is SPLASH_BLOCKS  # edges that do not divide: today's
    # the pairs the tiles cover at 8,192 positions under 512 keys: 3.9 times the window's at
    # today's edges, twice at these
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert pairs == 4_063_488
    assert (8 + 7) * 1024 ** 2 / pairs == pytest.approx(3.87, abs=0.01)
    assert (16 + 15) * 512 ** 2 / pairs == pytest.approx(2.0, abs=0.01)


def test_a_narrow_window_builds_three_kernels_and_a_wide_one_the_two_it_built():
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    build = causal_attention_module._splash_kernel
    major = causal_attention_module.SPLASH_BLOCK_MAJOR
    t = 2 * major

    def kernels(window):
        q = jax.ShapeDtypeStruct((1, 8, t, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 1, t, 128), jnp.bfloat16)
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            causal_attention_module._splash_causal_attention(
                q, k, v, 1.0, interpret=True, window=window).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, kv, kv))

    narrow, wide = kernels(512), kernels(major)
    assert narrow.count("pallas_call") == 3 and wide.count("pallas_call") == 2
    for name in ("splash_mqa_fwd", "splash_mqa_dkv", "splash_mqa_dq"):
        assert name in narrow
    assert "splash_mqa_dq" not in wide and "splash_mqa_dkv" in wide
    assert build(8, t, True, multi_query=True, window=512) is build(
        8, t, True, multi_query=True, window=512)
    # the mask is the issue's: i >= j and i - j < 512; 16 + 15 tiles of 512 at 8,192 positions
    local = masks.LocalMask((8192, 8192), window_size=(511, 0), offset=0)
    blocks = np.asarray(local[:, :]).reshape(16, 512, 16, 512)
    assert int(blocks.any(axis=(1, 3)).sum()) == 31
    i, j = np.arange(5000, 5003)[:, None], np.arange(8192)[None, :]
    np.testing.assert_array_equal(np.asarray(local[5000:5003, :]), (i >= j) & (i - j < 512))
