"""Milliseconds per captured step that device 0 spent in the gated short
convolutions, forward and backward: the mixer's two products
(``bagua_model/part=conv_proj``) and the gates and taps between them
(``conv_core``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "conv_proj", "conv_core")
