"""Collective operations executed per captured step on device 0 (an
asynchronous one counted once, at its start), from the program's summary of
the capture."""

from benchmark.step_summary import exchange


def read(context):
    return exchange(context, "calls") or None
