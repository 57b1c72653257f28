"""Milliseconds per captured step that device 0 spent in the held experts'
grouped products of ``solar-open2-250b``'s four expert layers, forward and
backward (``bagua_model/part=moe_experts``: three products of 4,096 by 1,280
over 8 groups of 205 expected rows, and the masks on their results), from the
program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_experts")
