"""The plain references against ``bagua_tpu.models`` in float32, at a toy
size on the CPU: the loss and every gradient leaf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest

#: float32 against float32 on the CPU.  VGG's reference runs the same
#: operations in the same order as the model and agrees to the bit; BERT's
#: takes layer norm's variance as the mean squared deviation where flax takes
#: E[x^2] - E[x]^2, and scans its layers, which rounds differently: 4.4e-7 of
#: a leaf's norm was the largest seen over five seeds.  1e-5 leaves rounding
#: twenty times that and holds a real disagreement (a wrong layout, a missing
#: term, bfloat16's 4e-3) far outside it.
TOLERANCE = {"bert-large": 1e-5, "vgg16": 1e-6}
CELL = {"bert-large": "bert-large.dp1", "vgg16": "vgg16.dp1"}


def program_model(name, sizes, dtype):
    """``(model, loss function, example input)`` of the program at ``sizes``
    with ``compute_dtype=dtype``: the adapters build it in bfloat16, which is
    the configuration, so the test that wants float32 builds it here."""
    if name == "bert-large":
        from bagua_tpu.models.bert import BertConfig, BertForPreTraining, mlm_loss_fn

        model = BertForPreTraining(BertConfig(
            vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
            num_layers=sizes["num_hidden_layers"], num_heads=sizes["num_attention_heads"],
            intermediate_size=sizes["intermediate_size"],
            max_position_embeddings=sizes["max_position_embeddings"],
            layer_norm_eps=sizes["layer_norm_eps"], compute_dtype=dtype))
        return model, mlm_loss_fn(model), jnp.zeros((2, sizes["seq_len"]), jnp.int32)
    from bagua_tpu.models.vgg import VGG, vgg_loss_fn

    model = VGG(num_classes=sizes["num_classes"], cfg=tuple(sizes["plan"]),
                compute_dtype=dtype, classifier_width=sizes["classifier_width"])
    side = sizes["image_size"]
    return model, vgg_loss_fn(model), jnp.zeros((1, side, side, 3), jnp.float32)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", sorted(TOLERANCE))
def test_reference_agrees_with_the_model_in_float32(name, seed):
    cell = manifest.load_cell(CELL[name], dry=True)
    sizes = cell.sizes
    k_params, k_batch = jax.random.split(jax.random.PRNGKey(seed))
    ref_params = cell.reference.init_params(k_params, sizes)
    batch = cell.adapter.draw_batch(k_batch, 4, sizes)
    # float32 storage throughout: the program tree without the bfloat16 cast
    program_params = cell.adapter.to_program(ref_params, sizes, cast=False)
    loss, grad = jax.value_and_grad(program_model(name, sizes, jnp.float32)[1])(program_params, batch)
    ref_loss, ref_grad = jax.value_and_grad(
        lambda p: cell.reference.loss(p, batch, sizes))(ref_params)
    ref_grad = cell.adapter.to_program(ref_grad, sizes, cast=False)
    tol = TOLERANCE[name]
    assert abs(float(loss) - float(ref_loss)) <= tol * abs(float(ref_loss))
    assert jax.tree.structure(grad) == jax.tree.structure(ref_grad)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grad), jax.tree.leaves(ref_grad)):
        scale = float(jnp.linalg.norm(want))
        assert scale > 0, f"{jax.tree_util.keystr(path)}: the reference's gradient is zero"
        err = float(jnp.linalg.norm(got - want)) / scale
        assert err <= tol, f"{jax.tree_util.keystr(path)}: {err:.3g} of the leaf's norm"


@pytest.mark.parametrize("name", sorted(TOLERANCE))
def test_weights_map_onto_the_programs_own_tree(name):
    """``to_program`` gives exactly the tree, shapes and storage types that
    the model's own ``init`` gives."""
    cell = manifest.load_cell(CELL[name], dry=True)
    sizes = cell.sizes
    mapped = jax.eval_shape(
        lambda k: cell.adapter.to_program(cell.reference.init_params(k, sizes), sizes),
        jax.random.PRNGKey(0))
    model, _, example = program_model(name, sizes, jnp.bfloat16)
    own = jax.eval_shape(lambda k: model.init(k, example)["params"], jax.random.PRNGKey(0))
    assert jax.tree.structure(mapped) == jax.tree.structure(own)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mapped), jax.tree.leaves(own)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), jax.tree_util.keystr(path)


def test_operation_counts_at_the_published_sizes():
    """The counts ``mfu_pct`` stands on, at the cells' own sizes: BERT-Large
    at sequence 128 is 87.2 GFLOP forward (the layers 78.9, the head 8.3),
    VGG16 at 224 pixels 15.47 G multiply-adds."""
    bert = manifest.load_cell("bert-large.dp1")
    assert bert.adapter.train_flops_per_sample(bert.sizes) == pytest.approx(3 * 87.19e9, rel=1e-3)
    vgg = manifest.load_cell("vgg16.dp1")
    assert vgg.adapter.train_flops_per_sample(vgg.sizes) == pytest.approx(3 * 2 * 15.47e9, rel=1e-3)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
            lambda k: bert.reference.init_params(k, bert.sizes), jax.random.PRNGKey(0))))
    assert n_params == pytest.approx(366.4e6, rel=1e-3)
