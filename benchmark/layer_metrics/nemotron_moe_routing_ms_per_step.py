"""Milliseconds per captured step that device 0 spent in the routing machinery
of ``nemotron-3-super``'s five expert layers, forward and backward: the router
over 512 experts on the hidden state (``bagua_model/part=moe_route``), the sort
of 22 choices a token and the gather that brings the held experts' rows
together in a buffer of eight rows a token (``moe_dispatch``) and the weighted
return to the tokens (``moe_combine``), from the program's summary of the
capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_route", "moe_dispatch", "moe_combine")
