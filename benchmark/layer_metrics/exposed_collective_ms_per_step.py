"""Milliseconds per captured step in which a collective operation ran on
device 0 and no other operation did: the exchange that compute does not
hide, from the profiler capture."""


def read(context):
    trace = context["trace"]
    if not trace or not trace["steps"] or not trace["collective_s"]:
        return None
    return 1e3 * trace["exposed_collective_s"] / trace["steps"]
