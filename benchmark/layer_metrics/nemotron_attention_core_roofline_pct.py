"""Share of the chip's bf16 peak at which the one attention layer's core ran:
``3 x 2 x 2 x 4 heads x 128 x 8192 x 8193 / 2`` operations, scores and mixing
over the pairs the causal mask leaves open, forward and backward,
recomputation not counted (the adapter's ``attention_core_flops_per_sample``),
over the time under ``bagua_model/part=attn_core``."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "nemotron_attention_core_roofline_pct",
                        "attention_core_flops_per_sample", "attn_core")
