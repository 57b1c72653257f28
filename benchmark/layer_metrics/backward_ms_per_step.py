"""Milliseconds per captured step that device 0 spent in operations traced
under ``bagua_step/phase=fwd_bwd`` inside autodiff's ``transpose(`` frame: the
backward pass without its collectives, from the program's summary of the
capture."""

from benchmark.step_summary import partition_ms


def read(context):
    return partition_ms(context, "backward")
