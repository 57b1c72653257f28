"""Milliseconds per captured step that device 0 spent under
``bagua_model/part=moe_experts`` in ``nemotron-3-super``: the held experts' two
grouped products in the latent width, their masks and the squared ReLU between
them (built again in the backward pass), forward and backward, from the
program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "moe_experts")
