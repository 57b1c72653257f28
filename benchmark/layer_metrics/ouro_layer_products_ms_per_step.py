"""Milliseconds per captured step that device 0 spent in the seven products of
``ouro-2.6b``'s layers, sixteen applications a step, forward, backward and
whatever of them the memory plan runs again: the four projections onto the
kernels' layout with the rotation (``bagua_model/part=attn_proj``) and the
MLP's three with the gate between them (``dense_mlp``), from the program's
summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "attn_proj", "dense_mlp")
