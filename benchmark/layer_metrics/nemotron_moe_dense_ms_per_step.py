"""Milliseconds per captured step that device 0 spent in the dense products of
``nemotron-3-super``'s five expert layers, forward and backward: the two latent
projections, 4,096 to 1,024 before dispatch and back after combine
(``bagua_model/part=moe_latent``), and the shared expert at the hidden width
(``moe_shared``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_latent", "moe_shared")
