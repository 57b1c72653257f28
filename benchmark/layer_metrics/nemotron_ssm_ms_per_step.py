"""Milliseconds per captured step that device 0 spent in the five Mamba-2
mixers of ``nemotron-3-super``, forward and backward: the two projections
(``bagua_model/part=ssm_proj``), the depthwise causal convolution with its
bias and SiLU (``ssm_conv``) and the core (``ssm_core``: the chunked scan, the
``D`` term, the gate and the group norm, and all of it built again in the
backward pass), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "ssm_proj", "ssm_conv", "ssm_core")
