"""Milliseconds per captured step that device 0 spent in operations traced
under ``bagua_step/phase=health``: the global gradient norm and the
non-finite count the step computes for the health monitor, from the
program's summary of the capture.  0 where the compiler fused all of it into
the operations that make the gradients.  None without a hub, whose step has
no such phase."""

from benchmark.step_summary import summary


def read(context):
    found = summary(context)
    if not found or not found["labeled"] or (
            "completions" not in context["counters"]["host_overhead"]):
        return None
    return found["partition_ms"].get("health", 0.0)
