"""Milliseconds per captured step that device 0 spent in the attention of
``smallthinker-21ba3b``'s four layers, forward and backward: the projections
onto the kernels' layout with the rotation where a layer has one
(``bagua_model/part=attn_proj``), the global layer's core (``attn_core``) and
the three windowed layers' (``attn_window_core``), from the program's summary
of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "attn_proj", "attn_core", "attn_window_core")
