"""Share of the chip's bf16 peak at which the held experts' grouped products
ran: the operations of the *expected* routed rows (2,816 a layer: 8,192 x 22 x
8 / 512), two products of 1,024 x 2,688, forward and backward (the adapter's
``moe_experts_flops_per_sample``), over the time under
``bagua_model/part=moe_experts``, which holds the passes over the buffer's
65,536 rows too."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "nemotron_moe_experts_roofline_pct",
                        "moe_experts_flops_per_sample", "moe_experts")
