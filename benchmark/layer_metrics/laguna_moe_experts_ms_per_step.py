"""Milliseconds per captured step that device 0 spent in the held experts'
grouped products of ``laguna-xs.2``'s four expert layers (32 groups, width 512
of 2,048), their masks and the SwiGLU between them, forward and backward
(``bagua_model/part=moe_experts``), from the program's summary of the
capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_experts")
