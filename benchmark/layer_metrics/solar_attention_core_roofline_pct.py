"""Share of the chip's bf16 peak at which the core of ``solar-open2-250b``'s
one GQA layer ran (8 query heads of 128 on 1 key-value head, no positions):
scores and mixing over the pairs the causal mask leaves open, forward and
backward (the adapter's ``attention_core_flops_per_sample``), over the time
under ``bagua_model/part=attn_core``."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "solar_attention_core_roofline_pct",
                        "attention_core_flops_per_sample", "attn_core")
