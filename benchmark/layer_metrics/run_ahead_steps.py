"""Steps dispatched and not yet seen to complete when the next one is sent,
as a mean over the window: the hub's waiter counts both ends
(``completions.run_ahead_mean`` of the engine's snapshot).  Under 1 means the
tracing empties the device's queue every step.  None without a hub."""


def read(context):
    completions = context["counters"]["host_overhead"].get("completions")
    return completions["run_ahead_mean"] if completions else None
