"""Share of the chip's bf16 peak at which the attention cores ran: the
operations of scores and mixing over every pair at or under the diagonal,
sixteen applications, forward and backward, nothing recomputed (the adapter's
``attention_core_flops_per_sample``), over the time under
``bagua_model/part=attn_core``.  Compute bounds it."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "ouro_attention_core_roofline_pct",
                        "attention_core_flops_per_sample", "attn_core")
