"""Share of the chip's bf16 peak at which the attention core ran: the
operations of scores and mixing at the causal half of the square, forward
and backward, nothing recomputed (the adapter's
``attention_core_flops_per_sample``), over the time under
``bagua_model/part=attn_core``.  Compute bounds it: at 8,192 positions the
core reads 0.25 GB a layer and multiplies 2 TFLOP."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "attention_core_roofline_pct",
                        "attention_core_flops_per_sample", "attn_core")
