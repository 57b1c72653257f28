"""Milliseconds per captured step that device 0 spent in the three KDA mixers
of ``solar-open2-250b``, forward and backward: the four wide products and the
four narrow ones (``bagua_model/part=kda_proj``), the three depthwise causal
convolutions with SiLU (``kda_conv``), the core (``kda_core``: the L2 norms,
the decay, ``beta`` and the chunked delta rule, and all of it built again in
the backward pass) and the head norm with its gate (``kda_gate_norm``), from
the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "kda_proj", "kda_conv", "kda_core", "kda_gate_norm")
