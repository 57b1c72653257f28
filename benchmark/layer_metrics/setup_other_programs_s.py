"""Seconds of tracing, lowering and backend compile (or cache load) of
every program compiled in the process before the window that is not a step
variant: the engine's small programs in ``init_state``, and the caller's own
(here the benchmark's makers, feed and checks, and every eager operation)."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "other_programs")
