"""Megabytes of operands of the collective operations of one captured step
on device 0, from the shapes in the instructions' text (the program's summary
of the capture)."""

from benchmark.step_summary import exchange


def read(context):
    nbytes = exchange(context, "bytes")
    return nbytes / 1e6 if nbytes else None
