"""Milliseconds per captured step that device 0 spent in the attention of
``laguna-xs.2``'s five layers, forward and backward: the projections onto the
kernels' layout with each layer's rotation (``bagua_model/part=attn_proj``),
the per-head output gate's product, sigmoid and multiplication
(``attn_gate``), the two global layers' cores at 48 query heads
(``attn_core``) and the three windowed layers' at 64 (``attn_window_core``),
from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "attn_proj", "attn_gate", "attn_core", "attn_window_core")
