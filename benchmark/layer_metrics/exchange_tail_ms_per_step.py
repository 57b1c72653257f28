"""Milliseconds per captured step in which a collective ran on device 0
after the backward pass's last operation had ended: near the whole of
``collective_ms_per_step`` if the exchange sits behind the backward pass,
near nothing if it sits inside it; from the program's summary of the
capture."""

from benchmark.step_summary import exchange


def read(context):
    if not exchange(context, "calls"):
        return None
    return exchange(context, "tail_ms")
