"""TP layers, ring attention, and the parallel BERT model.

Oracles: single-device full computation on the gathered inputs/weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bagua_tpu.parallel.ring_attention import ring_attention, _block_attention_local
from bagua_tpu.parallel.tensor_parallel import (
    ColumnParallelDense,
    ParallelMLP,
    RowParallelDense,
)

B, T, H, D = 2, 4, 4, 8  # batch, local seq, heads, head_dim
SP = 8


def sp_mesh(n=8, axis="sp"):
    devs = jax.devices()[:n]
    return Mesh(np.array(devs), (axis,))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(B, SP * T, H, D).astype(np.float32)
    k = rng.randn(B, SP * T, H, D).astype(np.float32)
    v = rng.randn(B, SP * T, H, D).astype(np.float32)

    full = np.asarray(
        _block_attention_local(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    )

    mesh = sp_mesh()
    fn = jax.jit(
        jax.shard_map(
            lambda qq, kk, vv: ring_attention(qq, kk, vv, axis_name="sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    got = np.asarray(fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, full, rtol=2e-4, atol=2e-5)


def test_ring_attention_kv_mask():
    """Padding mask rotates with the K/V blocks and matches the full oracle."""
    rng = np.random.RandomState(5)
    q = rng.randn(B, SP * T, H, D).astype(np.float32)
    k = rng.randn(B, SP * T, H, D).astype(np.float32)
    v = rng.randn(B, SP * T, H, D).astype(np.float32)
    mask = rng.rand(B, SP * T) > 0.3  # ~70% attendable

    full = np.asarray(
        _block_attention_local(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), kv_mask=jnp.asarray(mask)
        )
    )
    mesh = sp_mesh()
    fn = jax.jit(
        jax.shard_map(
            lambda qq, kk, vv, mm: ring_attention(qq, kk, vv, axis_name="sp", kv_mask=mm),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    got = np.asarray(fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(mask)))
    np.testing.assert_allclose(got, full, rtol=2e-4, atol=2e-5)


def test_ring_attention_single_rank_fallback():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    out = ring_attention(q, q, q, axis_name="sp")  # no bound axis -> local
    ref = _block_attention_local(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_column_row_parallel_matches_dense():
    """Column->gelu->Row over a 4-way tp axis == single-device dense MLP."""
    tp = 4
    rng = np.random.RandomState(2)
    x = rng.randn(6, 16).astype(np.float32)

    mlp = ParallelMLP(hidden_features=32, out_features=16, tp_size=tp, axis_name="tp")
    params = mlp.init(jax.random.PRNGKey(0), jnp.asarray(x))["params"]

    # oracle: assemble the full weight matrices from per-rank slices.
    # Per-rank params are identical after init (shapes are local); emulate
    # rank r holding columns [r*local:(r+1)*local] by initializing per rank.
    per_rank = [
        mlp.init(jax.random.PRNGKey(r), jnp.asarray(x))["params"] for r in range(tp)
    ]
    w1 = np.concatenate(
        [np.asarray(p["ColumnParallelDense_0"]["kernel"]) for p in per_rank], axis=1
    )
    b1 = np.concatenate(
        [np.asarray(p["ColumnParallelDense_0"]["bias"]) for p in per_rank]
    )
    w2 = np.concatenate(
        [np.asarray(p["RowParallelDense_0"]["kernel"]) for p in per_rank], axis=0
    )
    b2 = sum(np.asarray(p["RowParallelDense_0"]["bias"]) for p in per_rank)

    expect = jax.nn.gelu(x @ w1 + b1) @ w2 + b2

    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    fn = jax.jit(
        jax.shard_map(
            lambda p, xx: mlp.apply({"params": jax.tree.map(lambda q: q[0], p)}, xx),
            mesh=mesh,
            in_specs=(P("tp"), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
    got = np.asarray(fn(stacked, jnp.asarray(x)))
    np.testing.assert_allclose(got, np.asarray(expect), rtol=2e-3, atol=2e-4)


def test_tp_axis_mismatch_raises():
    mlp = ParallelMLP(hidden_features=32, out_features=16, tp_size=4, axis_name="tp")
    x = jnp.zeros((2, 16))
    params = mlp.init(jax.random.PRNGKey(0), x)["params"]
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="tp_size=4"):
        jax.jit(
            jax.shard_map(
                lambda xx: mlp.apply({"params": params}, xx),
                mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
            )
        )(x)


@pytest.mark.slow
def test_bert_forward_shapes_and_parallel_consistency():
    """BERT with tp=2 x sp=2 on a 2x2 submesh matches the single-device
    model with assembled weights — end-to-end integration of TP + SP."""
    from bagua_tpu.models.bert import BertConfig, BertModel

    vocab, hidden, heads, layers = 64, 16, 4, 2
    seq = 8
    rng = np.random.RandomState(3)
    ids = rng.randint(0, vocab, size=(2, seq)).astype(np.int32)

    # single-device reference
    cfg0 = BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        intermediate_size=32, max_position_embeddings=seq,
    )
    model0 = BertModel(cfg0)
    params0 = model0.init(jax.random.PRNGKey(0), jnp.asarray(ids))["params"]
    ref = np.asarray(model0.apply({"params": params0}, jnp.asarray(ids)))

    # tp=2, sp=2 model: slice params0 into per-(tp,sp)-rank shards
    tp, sp = 2, 2
    cfg = BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        intermediate_size=32, max_position_embeddings=seq, tp_size=tp, tp_axis="tp",
        sp_axis="sp",
    )
    model = BertModel(cfg)

    def shard_for_tp(r):
        """Take tp-rank r's slice of every TP param; heads are contiguous."""

        def slice_leaf(path, leaf):
            name = jax.tree_util.keystr(path)
            arr = np.asarray(leaf)
            if "qkv" in name:
                if name.endswith("['kernel']"):
                    # (in, 3*hidden) -> 3 x heads x head_dim; take local heads
                    k3 = arr.reshape(arr.shape[0], 3, heads, hidden // heads)
                    loc = k3[:, :, r * (heads // tp) : (r + 1) * (heads // tp)]
                    return jnp.asarray(loc.reshape(arr.shape[0], -1))
                loc = arr.reshape(3, heads, hidden // heads)[
                    :, r * (heads // tp) : (r + 1) * (heads // tp)
                ]
                return jnp.asarray(loc.reshape(-1))
            if "['out']['kernel']" in name:
                rows = arr.shape[0] // tp
                return jnp.asarray(arr[r * rows : (r + 1) * rows])
            if "['out']['bias']" in name:
                # RowParallelDense adds the bias AFTER the psum on every
                # rank, so the per-rank shard is the full bias.
                return jnp.asarray(arr)
            if "ColumnParallelDense_0" in name:
                cols = arr.shape[-1] // tp
                return jnp.asarray(arr[..., r * cols : (r + 1) * cols])
            if "RowParallelDense_0" in name and name.endswith("['kernel']"):
                rows = arr.shape[0] // tp
                return jnp.asarray(arr[r * rows : (r + 1) * rows])
            if "RowParallelDense_0" in name and name.endswith("['bias']"):
                return jnp.asarray(arr)
            return jnp.asarray(arr)

        return jax.tree_util.tree_map_with_path(slice_leaf, params0)

    per_tp = [shard_for_tp(r) for r in range(tp)]
    # build (tp*sp) rank-stacked params: same tp shard for both sp ranks
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[per_tp[r] for r in (0, 1) for _ in range(sp)]
    )

    devs = np.array(jax.devices()[:4]).reshape(tp, sp)
    mesh = Mesh(devs, ("tp", "sp"))
    fn = jax.jit(
        jax.shard_map(
            lambda p, ii: model.apply({"params": jax.tree.map(lambda q: q[0], p)}, ii),
            mesh=mesh,
            in_specs=(P(("tp", "sp")), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    got = np.asarray(fn(stacked, jnp.asarray(ids)))
    np.testing.assert_allclose(got, ref, rtol=5e-3, atol=5e-3)


# -- the fused query-key-value result held where its product wrote it (models/bert.py, PR 51) --


def _bert_cut(**settings):
    """``bert_base_config`` widths cut to two layers and a small vocabulary
    (the attention layer meets neither)."""
    import dataclasses
    from bagua_tpu.models.bert import bert_base_config

    return dataclasses.replace(bert_base_config(), num_layers=2, vocab_size=512,
                               max_position_embeddings=256, **settings)


def _as_the_layer_was(monkeypatch):
    """``models/bert.py`` with its attention layer written as it stood before
    PR 51: the oracle of what another layer's text was."""
    import flax.linen as nn
    from bagua_tpu.models import bert

    class BertSelfAttention(nn.Module):
        cfg: bert.BertConfig

        @nn.compact
        def __call__(self, x, mask=None):
            cfg = self.cfg
            b, t, _ = x.shape
            local_heads = cfg.num_heads // cfg.tp_size
            head_dim = cfg.hidden_size // cfg.num_heads
            qkv = ColumnParallelDense(3 * cfg.hidden_size, cfg.tp_size, cfg.tp_axis,
                                      dtype=cfg.compute_dtype, name="qkv")(x)
            qkv = qkv.reshape(b, t, 3, local_heads, head_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if cfg.sp_axis is not None:
                ctx = ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=False, kv_mask=mask)
            else:
                ctx = _block_attention_local(q, k, v, causal=False, kv_mask=mask)
            ctx = ctx.reshape(b, t, local_heads * head_dim)
            return RowParallelDense(cfg.hidden_size, cfg.tp_size, cfg.tp_axis,
                                    dtype=cfg.compute_dtype, name="out")(ctx)

    monkeypatch.setattr(bert, "BertSelfAttention", BertSelfAttention)


def _layout_constraints(jaxpr):
    return sum(eqn.primitive.name == "layout_constraint" for eqn in jaxpr.eqns) + sum(
        _layout_constraints(sub) for eqn in jaxpr.eqns for sub in jax.core.jaxprs_in_params(eqn.params))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("tokens", [(2, 128), (1, 256)], ids=["2x128", "1x256"])
def test_bert_held_layer_is_the_layer_it_was_bit_for_bit(tokens, dtype, monkeypatch):
    """The layer that holds the fused product's result to its layout and joins
    the three gradients itself changes no arithmetic: steered onto the TPU's
    path (the constraint itself is one the CPU takes too), the loss and every
    leaf's gradient are those of the layer as it stood, to the bit."""
    from bagua_tpu.models import bert
    from tests.helpers import compiled

    model = bert.BertForPreTraining(_bert_cut(compute_dtype=dtype))
    ids = jax.random.randint(jax.random.PRNGKey(5), tokens, 0, 512)
    labels = jax.random.randint(jax.random.PRNGKey(6), tokens, 0, 512)
    params = model.init(jax.random.PRNGKey(7), ids)["params"]

    def step():  # a function of its own a trace: a traced one is not traced again
        return jax.value_and_grad(bert.mlm_loss_fn(bert.BertForPreTraining(model.cfg)))

    with monkeypatch.context() as steered:
        steered.setattr(jax, "default_backend", lambda: "tpu")
        held = jax.make_jaxpr(step())(params, (ids, labels))
        loss, grads = compiled(step(), params, (ids, labels))
    assert _layout_constraints(held.jaxpr) == 2 * 2  # a layer's result and its cotangent
    assert _layout_constraints(jax.make_jaxpr(step())(params, (ids, labels)).jaxpr) == 0
    _as_the_layer_was(monkeypatch)
    want_loss, want = compiled(step(), params, (ids, labels))
    assert np.array_equal(np.asarray(loss), np.asarray(want_loss))
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, got), expected in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert got.dtype == expected.dtype and np.array_equal(
            np.asarray(got, np.float32), np.asarray(expected, np.float32)), jax.tree_util.keystr(path)
    assert float(jnp.abs(grads["bert"]["layer_0"]["attention"]["qkv"]["kernel"].astype(jnp.float32)).max()) > 0


def test_bert_parameter_tree_is_leaf_for_leaf_what_it_was(monkeypatch):
    """``benchmark/configs/bert-large.py`` builds the tree leaf by leaf: no
    name, shape or type of it moves with the path a layer takes."""
    from bagua_tpu.models import bert

    cfg = _bert_cut(compute_dtype=jnp.bfloat16)
    ids = jnp.zeros((2, 128), jnp.int32)

    def tree():
        shapes = jax.eval_shape(lambda: bert.BertForPreTraining(cfg).init(jax.random.PRNGKey(0), ids))
        return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
                for path, leaf in jax.tree_util.tree_leaves_with_path(shapes["params"])}

    here = tree()
    with monkeypatch.context() as steered:
        steered.setattr(jax, "default_backend", lambda: "tpu")
        on_chip = tree()
    _as_the_layer_was(monkeypatch)
    assert here == on_chip == tree()
    attention = {name.split("['attention']")[1]: leaf for name, leaf in here.items()
                 if "['layer_1']['attention']" in name}
    assert attention == {
        "['qkv']['kernel']": ((768, 2304), jnp.bfloat16), "['qkv']['bias']": ((2304,), jnp.bfloat16),
        "['out']['kernel']": ((768, 768), jnp.bfloat16), "['out']['bias']": ((768,), jnp.bfloat16)}


#: settings of the configuration, whether a key mask is passed, the mesh's axes, and the layout
#: constraints a layer and pass on a TPU
_BERT_LAYERS = {
    "one_rank": (dict(), False, None, 1),
    "tp2": (dict(tp_size=2, tp_axis="tp"), False, ("tp",), 1),  # the same columns, half the heads
    "sp2": (dict(sp_axis="sp"), False, ("sp",), 0),             # ring attention
    "kv_mask": (dict(), True, None, 0),
    "heads_of_128": (dict(num_heads=6), False, None, 0),        # whole lane tiles: nothing to hold
}


@pytest.mark.parametrize("case", list(_BERT_LAYERS))
def test_bert_layers_that_hold_nothing_lower_to_the_text_they_lowered_to(case, monkeypatch):
    """The path is chosen by the layer's own fields and the backend: the local
    core at heads narrower than a lane tile, on a TPU.  Here, on the CPU, every
    layer lowers to the text of the layer as it stood; steered to the TPU, ring
    attention, a key mask and heads of whole lane tiles still do, and the rest
    hold the product's result and its cotangent."""
    from bagua_tpu.models import bert

    settings, masked, axes, constraints = _BERT_LAYERS[case]
    cfg = _bert_cut(compute_dtype=jnp.bfloat16, **settings)
    ids = jnp.zeros((2, 128), jnp.int32)
    mask = jnp.ones((2, 128), bool) if masked else None

    def lowered():
        model = bert.BertModel(cfg)

        def loss(params, ids):
            return jnp.sum(model.apply({"params": params}, ids, None, mask))

        def grad(params, ids):
            g = jax.grad(loss)(params, ids)
            return jax.tree.map(lambda a: a[None], g) if axes else g

        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
        if axes:  # every rank its own parameters, stacked as the engine holds them
            mesh = Mesh(np.array(jax.devices()[:2]), axes)
            params = jax.tree.map(lambda a: jax.ShapeDtypeStruct((2,) + a.shape, a.dtype), params)
            fn = jax.shard_map(lambda p, i: grad(jax.tree.map(lambda a: a[0], p), i), mesh=mesh,
                               in_specs=(P(axes), P()), out_specs=P(axes), check_vma=False)
        else:
            fn = grad
        return (jax.jit(fn).lower(params, ids).as_text(),
                _layout_constraints(jax.make_jaxpr(fn)(params, ids).jaxpr))

    text, found = lowered()
    with monkeypatch.context() as steered:
        steered.setattr(jax, "default_backend", lambda: "tpu")
        on_chip_text, on_chip = lowered()
    _as_the_layer_was(monkeypatch)
    was, _ = lowered()
    assert found == 0 and text == was
    assert on_chip == 2 * 2 * constraints
    assert (on_chip_text == was) == (constraints == 0)


def test_flash_block_pallas_matches_jnp():
    """The Pallas block kernel (interpret mode on CPU) reproduces the jnp
    reference contribution exactly up to float tolerance, incl. padding of
    t_q/t_k/d to TPU tiles and fully-masked columns."""
    from bagua_tpu.kernels.flash_attention import (
        block_attention,
        block_attention_pallas,
    )

    rng = np.random.RandomState(0)
    b, tq, tk, h, d = 2, 12, 20, 3, 24  # deliberately non-tile-aligned
    qf = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    mask = jnp.asarray(rng.rand(b, tq, tk) > 0.3)
    mask = mask.at[0, 3, :].set(False)  # one fully-masked query row

    o_ref, l_ref, m_ref = block_attention(qf, k, v, mask)
    o_p, l_p, m_p = block_attention_pallas(qf, k, v, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "tq,tk,d,bq,bk,masktype",
    [
        (384, 640, 64, 128, 128, "causal"),    # 3x5 k-accumulating tiles
        (256, 512, 128, 128, 256, "full"),     # 2x2 tiles
        (200, 300, 64, 128, 128, "causal"),    # unaligned seqs: pad + tile
        (256, 256, 128, 512, 512, "firstcol"), # blocks > seq: single tile
    ],
)
@pytest.mark.slow
def test_flash_tiled_multi_block_matches_jnp(tq, tk, d, bq, bk, masktype):
    """The TILED kernel's online-softmax accumulation across the sequential
    k-grid must reproduce the jnp reference for every tiling regime —
    multi-tile causal, full, unaligned-with-padding, and rows where only the
    first key survives (running-max rescale correctness)."""
    from bagua_tpu.kernels.flash_attention import (
        block_attention,
        block_attention_pallas,
    )

    rng = np.random.RandomState(0)
    b, h = 1, 2
    qf = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32)) / np.sqrt(d)
    k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
    if masktype == "causal":
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq), (b, tq, tk)
        )
    elif masktype == "firstcol":
        mask = jnp.zeros((b, tq, tk), bool).at[:, :, 0].set(True)
    else:
        mask = jnp.ones((b, tq, tk), bool)
    o_p, l_p, m_p = block_attention_pallas(
        qf, k, v, mask, interpret=True, block_q=bq, block_k=bk
    )
    o_j, l_j, m_j = block_attention(qf, k, v, mask)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_j), atol=2e-4)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_j), atol=2e-4)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_j), atol=2e-5)


def test_ring_attention_pallas_matches_oracle():
    """Full ring attention with the Pallas block kernel (interpret mode)
    equals full attention on the gathered sequence."""
    rng = np.random.RandomState(1)
    b, t, h, d, sp = 2, 16, 2, 8, 4
    q = rng.randn(b, t, h, d).astype(np.float32)
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    ref = np.asarray(
        _block_attention_local(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    )

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    fn = jax.jit(
        jax.shard_map(
            lambda qq, kk, vv: ring_attention(
                qq, kk, vv, axis_name="sp", causal=True,
                use_pallas=True, interpret=True,
            ),
            mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"), check_vma=False,
        )
    )
    got = np.asarray(fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_pallas_trains():
    """jax.grad through ring attention with the Pallas kernel must work
    (pallas_call has no autodiff rule — block_attention_fused carries a
    custom VJP) and match the jnp path's gradients.  Guards the training
    path that flips on the moment PALLAS_TPU.json validates the kernel."""
    rng = np.random.RandomState(3)
    b, t, h, d, sp = 1, 16, 2, 8, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))

    def make_loss(use_pallas):
        def loss(q, k, v):
            y = jax.shard_map(
                lambda qq, kk, vv: ring_attention(
                    qq, kk, vv, axis_name="sp", causal=True,
                    use_pallas=use_pallas, interpret=use_pallas,
                ),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"), check_vma=False,
            )(q, k, v)
            return jnp.sum(y ** 2)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    g_pallas = make_loss(True)(q, k, v)
    g_jnp = make_loss(False)(q, k, v)
    for gp, gj in zip(g_pallas, g_jnp):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_gqa_native_fused_matches_jnp(layout, monkeypatch):
    """GQA through the fused kernel WITHOUT jnp.repeat (K/V BlockSpecs index
    the shared head tiles; dk/dv accumulate over the query-head group axis):
    composed forward+backward gradients must match the jnp repeat path."""
    monkeypatch.setenv("BAGUA_PALLAS_FLASH_BWD", "1")
    rng = np.random.RandomState(5)
    b, t, h, hkv, d, sp = 1, 32, 4, 2, 8, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, hkv, d).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))

    def make_grad(use_pallas):
        def loss(q, k, v):
            y = jax.shard_map(
                lambda qq, kk, vv: ring_attention(
                    qq, kk, vv, axis_name="sp", causal=True,
                    kv_groups=h // hkv, layout=layout,
                    use_pallas=use_pallas, interpret=use_pallas,
                ),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"), check_vma=False,
            )(q, k, v)
            return jnp.sum(jnp.sin(y))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    for gp, gj in zip(make_grad(True)(q, k, v), make_grad(False)(q, k, v)):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_fused_backward_matches_jnp(layout, monkeypatch):
    """The FUSED flash backward (tile-recomputed probabilities, stop-grad-m
    semantics) must produce the same composed ring-attention gradients as
    the jnp path — the max-shift terms cancel under the merge+normalize
    composition, which is exactly what this pins."""
    monkeypatch.setenv("BAGUA_PALLAS_FLASH_BWD", "1")
    rng = np.random.RandomState(7)
    b, t, h, d, sp = 1, 32, 2, 8, 4
    q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))

    def make_grad(use_pallas):
        def loss(q, k, v):
            y = jax.shard_map(
                lambda qq, kk, vv: ring_attention(
                    qq, kk, vv, axis_name="sp", causal=True, layout=layout,
                    use_pallas=use_pallas, interpret=use_pallas,
                ),
                mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"), check_vma=False,
            )(q, k, v)
            return jnp.sum(jnp.sin(y))  # nontrivial downstream cotangent

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    g_fused = make_grad(True)(q, k, v)
    g_jnp = make_grad(False)(q, k, v)
    for gp, gj in zip(g_fused, g_jnp):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.slow
def test_gpt_4d_parallel_example():
    """The dp x pp x tp x sp composition example trains: one jitted step over
    a 4-axis mesh (pipeline stages, tensor-parallel blocks, ring attention,
    data parallel) with finite decreasing loss."""
    import subprocess
    import sys as _sys

    r = subprocess.run(
        [_sys.executable, "-c", (
            "import jax; jax.config.update('jax_platforms', 'cpu');"
            "import sys; sys.path.insert(0, '/root/repo');"
            "sys.path.insert(0, '/root/repo/examples/gpt_pretrain');"
            "from main import main;"
            "losses = main(['--steps', '5']);"
            "assert all(l == l for l in losses), losses;"
            "import numpy as np;"
            "assert np.mean(losses[-2:]) < losses[0], losses;"
            "print('4D OK', losses[0], '->', losses[-1])"
        )],
        env={**__import__('os').environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "4D OK" in r.stdout


def test_ring_attention_zigzag_matches_full():
    """Zigzag layout (balanced causal schedule): permute the sequence with
    zigzag_order, run the ring, invert — must equal full attention."""
    from bagua_tpu.parallel.ring_attention import zigzag_inverse, zigzag_order

    rng = np.random.RandomState(1)
    Tg = SP * T
    q = rng.randn(B, Tg, H, D).astype(np.float32)
    k = rng.randn(B, Tg, H, D).astype(np.float32)
    v = rng.randn(B, Tg, H, D).astype(np.float32)

    full = np.asarray(
        _block_attention_local(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    )

    order = zigzag_order(Tg, SP)
    inv = zigzag_inverse(Tg, SP)
    mesh = sp_mesh()
    fn = jax.jit(
        jax.shard_map(
            lambda qq, kk, vv: ring_attention(
                qq, kk, vv, axis_name="sp", causal=True, layout="zigzag"
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    got_z = np.asarray(fn(jnp.asarray(q[:, order]), jnp.asarray(k[:, order]),
                          jnp.asarray(v[:, order])))
    np.testing.assert_allclose(got_z[:, inv], full, rtol=2e-4, atol=2e-5)


def test_ring_attention_zigzag_kv_mask():
    """Zigzag with a key-padding mask (mask permutes with the sequence)."""
    from bagua_tpu.parallel.ring_attention import zigzag_inverse, zigzag_order

    rng = np.random.RandomState(2)
    Tg = SP * T
    q = rng.randn(B, Tg, H, D).astype(np.float32)
    k = rng.randn(B, Tg, H, D).astype(np.float32)
    v = rng.randn(B, Tg, H, D).astype(np.float32)
    mask = rng.rand(B, Tg) > 0.3

    full = np.asarray(
        _block_attention_local(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
            kv_mask=jnp.asarray(mask),
        )
    )

    order = zigzag_order(Tg, SP)
    inv = zigzag_inverse(Tg, SP)
    mesh = sp_mesh()
    fn = jax.jit(
        jax.shard_map(
            lambda qq, kk, vv, mm: ring_attention(
                qq, kk, vv, axis_name="sp", causal=True, kv_mask=mm, layout="zigzag"
            ),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )
    got_z = np.asarray(fn(
        jnp.asarray(q[:, order]), jnp.asarray(k[:, order]),
        jnp.asarray(v[:, order]), jnp.asarray(mask[:, order]),
    ))
    # rows whose every key is masked are implementation-defined; compare the rest
    valid = np.isfinite(full).all(axis=(2, 3))
    np.testing.assert_allclose(got_z[:, inv][valid], full[valid], rtol=2e-4, atol=2e-5)


def test_zigzag_order_roundtrip():
    from bagua_tpu.parallel.ring_attention import zigzag_inverse, zigzag_order

    order = zigzag_order(32, 4)
    inv = zigzag_inverse(32, 4)
    assert (order[inv] == np.arange(32)).all()
    assert (np.sort(order) == np.arange(32)).all()
    # rank 0's shard = half-blocks 0 and 7
    assert list(order[:8]) == list(range(4)) + list(range(28, 32))


# ---------------------------------------------------------------------------
# Fused collective matmul in the TP layers
# ---------------------------------------------------------------------------


def _mlp_per_rank(tp, fused, hidden=32, out=16):
    """ParallelMLP + per-rank-initialized stacked params (rank r holds its
    weight slice) — the suite's standard TP harness."""
    rng = np.random.RandomState(10)
    x = rng.randn(8, 16).astype(np.float32)
    mlp = ParallelMLP(
        hidden_features=hidden, out_features=out, tp_size=tp, axis_name="tp",
        fused=fused,
    )
    per_rank = [
        mlp.init(jax.random.PRNGKey(r), jnp.asarray(x))["params"] for r in range(tp)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    return mlp, stacked, x


def _mlp_apply(mlp, tp):
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    return jax.jit(
        jax.shard_map(
            lambda p, xx: mlp.apply({"params": jax.tree.map(lambda q: q[0], p)}, xx),
            mesh=mesh,
            in_specs=(P("tp"), P()),
            out_specs=P(),
            check_vma=False,
        )
    )


def _census(lowerable, *args):
    """HLO collective census via the perf-audit helper (the same counter the
    CI lane gates on)."""
    import os
    import sys

    ci = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ci")
    if ci not in sys.path:
        sys.path.insert(0, ci)
    from perf_audit import census

    hlo = jax.jit(lowerable).lower(*args).compile().as_text()
    return {op: entry["count"] for op, entry in census(hlo).items() if op != "copy"}


@pytest.mark.parametrize("fused", [True, "auto"])
def test_fused_mlp_matches_unfused(fused):
    """fused ParallelMLP == unfused on the same per-rank params."""
    tp = 4
    mlp_u, stacked, x = _mlp_per_rank(tp, False)
    mlp_f, _, _ = _mlp_per_rank(tp, fused)
    ref = np.asarray(_mlp_apply(mlp_u, tp)(stacked, jnp.asarray(x)))
    got = np.asarray(_mlp_apply(mlp_f, tp)(stacked, jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_tp_wire_census_fused_vs_unfused():
    """The autodiff wire contract of the Column->Row pair under shard_map.

    Unfused: the Megatron conjugate pair — EXACTLY one forward all-reduce
    plus one backward (psum's transpose on the input gradient), so 1 in the
    forward census and 2 in forward+backward.  Fused: ZERO standalone
    psum/all-reduce anywhere; the matmul_rs ring's tp_size-1 collective
    permutes (mirrored under autodiff) plus the row-block all-gather (whose
    transpose is a reduce-scatter) carry the exchange instead.
    """
    tp = 8
    mlp_u, stacked, x = _mlp_per_rank(tp, False)
    mlp_f, _, _ = _mlp_per_rank(tp, "auto")
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    xj = jnp.asarray(x)

    def wire(mlp, grad):
        def fwd(p, xx):
            return mlp.apply({"params": jax.tree.map(lambda q: q[0], p)}, xx)

        if grad:
            # grad wrt params AND input, nonlinear loss: the input cotangent
            # is what forces the backward collective onto the wire.
            inner = jax.grad(lambda p, xx: jnp.sum(fwd(p, xx) ** 2), argnums=(0, 1))
            out_specs = (P("tp"), P())
        else:
            inner, out_specs = fwd, P()
        return _census(
            jax.shard_map(
                inner, mesh=mesh, in_specs=(P("tp"), P()), out_specs=out_specs,
                check_vma=False,
            ),
            stacked, xj,
        )

    assert wire(mlp_u, grad=False).get("all-reduce") == 1
    assert wire(mlp_u, grad=True).get("all-reduce") == 2

    fwd_f = wire(mlp_f, grad=False)
    bwd_f = wire(mlp_f, grad=True)
    for c in (fwd_f, bwd_f):
        assert "all-reduce" not in c, c
    assert fwd_f["collective-permute"] == tp - 1, fwd_f
    assert fwd_f["all-gather"] == 1, fwd_f
    assert bwd_f["collective-permute"] == 2 * (tp - 1), bwd_f
    assert bwd_f["all-gather"] == 1 and bwd_f["reduce-scatter"] == 1, bwd_f


def test_fused_indivisible_tokens():
    """fused=True demands ring divisibility; 'auto' silently falls back."""
    tp = 4
    rng = np.random.RandomState(11)
    x = rng.randn(6, 16).astype(np.float32)  # 6 tokens % 4 != 0
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))

    def apply_with(fused):
        layer = RowParallelDense(12, tp, "tp", fused=fused)
        per_rank = [
            layer.init(jax.random.PRNGKey(r), jnp.asarray(x))["params"]
            for r in range(tp)
        ]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
        return jax.jit(
            jax.shard_map(
                lambda p, xx: layer.apply(
                    {"params": jax.tree.map(lambda q: q[0], p)}, xx
                ),
                mesh=mesh, in_specs=(P("tp"), P()), out_specs=P(),
                check_vma=False,
            )
        )(stacked, jnp.asarray(x))

    with pytest.raises(ValueError, match="divide by tp_size"):
        apply_with(True)
    got = np.asarray(apply_with("auto"))
    ref = np.asarray(apply_with(False))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("fused", [False, "auto"])
def test_sequence_parallel_roundtrip(fused):
    """Row(scatter_output) -> Column(gather_input): the sequence-parallel
    layout round-trips, fused and unfused agreeing with each other."""
    import flax.linen as nn

    tp = 4

    class Pair(nn.Module):
        fused: object

        @nn.compact
        def __call__(self, x):
            y = RowParallelDense(
                12, tp, "tp", fused=self.fused, scatter_output=True
            )(x)
            return ColumnParallelDense(
                8, tp, "tp", fused=self.fused, gather_input=True
            )(y)

    rng = np.random.RandomState(12)
    x = rng.randn(8, 20).astype(np.float32)  # (tokens, k_local) per rank
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))

    def run(fused_val):
        pair = Pair(fused=fused_val)
        # init with the LOCAL shard shape: RowParallelDense consumes the
        # k-sliced hidden, so its kernel is sized off x's local last dim
        x_local = jnp.asarray(x[:, : x.shape[1] // tp])
        per_rank = [
            pair.init(jax.random.PRNGKey(r), x_local)["params"] for r in range(tp)
        ]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
        return np.asarray(
            jax.jit(
                jax.shard_map(
                    lambda p, xx: pair.apply(
                        {"params": jax.tree.map(lambda q: q[0], p)}, xx
                    ),
                    mesh=mesh,
                    in_specs=(P("tp"), P(None, "tp")),
                    out_specs=P(None, "tp"),
                    check_vma=False,
                )
            )(stacked, jnp.asarray(x))
        )

    got = run(fused)
    assert got.shape == (8, 8)
    if fused != False:  # noqa: E712 — tri-state knob
        np.testing.assert_allclose(got, run(False), rtol=2e-5, atol=2e-5)
