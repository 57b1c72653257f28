"""Host milliseconds per step of the window that the dispatching thread
waited for the *device* on the tracing's account (``health_wait`` of the
engine's counters): the health monitor's read where an action is registered,
and a full hand-over queue.  0 where the monitor reads a step late.  None
without a hub."""


def read(context):
    snapshot = context["counters"]["host_overhead"]
    if "completions" not in snapshot:
        return None
    return snapshot.get("health_wait_ms_per_step")
