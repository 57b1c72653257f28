"""Share of the chip's bf16 peak at which the two global attention cores ran:
the operations of scores and mixing over the causal mask's 33,558,528 pairs a
layer, 48 query heads, forward and backward, nothing recomputed (the adapter's
``attention_core_flops_per_sample``), over the time under
``bagua_model/part=attn_core``.  Compute bounds it."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "laguna_full_attention_core_roofline_pct",
                        "attention_core_flops_per_sample", "attn_core")
