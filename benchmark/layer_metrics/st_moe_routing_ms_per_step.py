"""Milliseconds per captured step that device 0 spent in the routing
machinery of ``smallthinker-21ba3b``'s expert layers, forward and backward:
the router, fed before attention (``bagua_model/part=moe_route``), the sort and
gather that bring each held expert's rows together in a buffer of six rows a
token (``moe_dispatch``) and the weighted return to the tokens
(``moe_combine``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_route", "moe_dispatch", "moe_combine")
