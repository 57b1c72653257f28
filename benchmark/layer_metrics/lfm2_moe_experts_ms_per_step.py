"""Milliseconds per captured step that device 0 spent in the grouped matrix
products of the held experts (``bagua_model/part=moe_experts``), forward and
backward, from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_experts")
