"""Milliseconds per captured step that device 0 spent in ``ouro-2.6b``'s four
exits, forward, backward and what the memory plan runs again: each pass's
head product and cross entropy over 49,152 columns (``bagua_model/part=head``)
and its gate's product, the exit distribution, the weighted sum and the
entropy (``exit_gate``), from the program's summary of the capture."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "head", "exit_gate")
