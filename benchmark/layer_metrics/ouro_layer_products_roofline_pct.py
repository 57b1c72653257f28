"""Share of the chip's bf16 peak at which the layers' seven products ran: the
operations of sixteen applications of a layer, forward and backward, nothing
recomputed (the adapter's ``layer_products_flops_per_sample``: ``3 x 2 x 8192
x (4 x 2048^2 + 3 x 2048 x 5632) x 16``), over the time under
``bagua_model/part=attn_proj`` and ``dense_mlp``.  Compute bounds it."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "ouro_layer_products_roofline_pct",
                        "layer_products_flops_per_sample", "attn_proj", "dense_mlp")
