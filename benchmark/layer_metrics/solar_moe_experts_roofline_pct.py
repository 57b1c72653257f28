"""Share of the chip's bf16 peak at which the held experts' grouped products
ran: the operations of the *expected* routed rows (1,638 a layer, 205 an
expert), forward and backward (the adapter's ``moe_experts_flops_per_sample``),
over the time under ``bagua_model/part=moe_experts``."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "solar_moe_experts_roofline_pct",
                        "moe_experts_flops_per_sample", "moe_experts")
