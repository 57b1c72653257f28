"""Milliseconds per captured step in which a collective operation
(all-reduce, reduce-scatter, all-gather, all-to-all, collective-permute) ran
on device 0, from the profiler capture."""


def read(context):
    trace = context["trace"]
    if not trace or not trace["steps"] or not trace["collective_s"]:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
