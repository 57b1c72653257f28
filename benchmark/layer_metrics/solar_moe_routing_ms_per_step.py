"""Milliseconds per captured step that device 0 spent moving rows in
``solar-open2-250b``'s four expert layers, forward and backward: the router's
float32 product over 320 outputs, ``top_k`` of 8 and the chosen scores
(``bagua_model/part=moe_route``), the sorts, the group sizes of the 8 held
experts and ``spread`` into the 65,536-row buffer of 4,096 columns
(``moe_dispatch``), and ``collect`` with the router's weights
(``moe_combine``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_route", "moe_dispatch", "moe_combine")
