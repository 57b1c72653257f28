"""Share of the chip's bf16 peak at which the four exits' head products ran:
``3 x 2 x 8192 x 2048 x 49152`` an exit, forward and backward, the logits the
memory plan builds again not counted (the adapter's ``head_flops_per_sample``),
over the time under ``bagua_model/part=head``, which holds the cross entropy's
passes over the logits too.  Compute bounds it."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "ouro_head_roofline_pct", "head_flops_per_sample", "head")
