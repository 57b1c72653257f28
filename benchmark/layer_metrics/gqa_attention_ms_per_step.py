"""Milliseconds per captured step that device 0 spent in grouped-query
attention, forward and backward: the four projections, the norms of the heads
and the rotary embedding (``bagua_model/part=attn_proj``) and the attention
core (``attn_core``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "attn_proj", "attn_core")
