"""Host milliseconds per step of the window outside the four phases of
``host_ms_per_step``: the fit loop's body outside ``next()`` and ``train_step``
(``loop``), the telemetry hub's and the health monitor's work after the
dispatch, and building a step on a jit-cache miss; from the engine's own
counters, reset at the window's start.  The two metrics add: neither counts
the other's."""


def read(context):
    snapshot = context["counters"]["host_overhead"]
    keys = [f"{phase}_ms_per_step" for phase in ("loop", "telemetry", "health", "build")]
    if not context["trace"] or any(key not in snapshot for key in keys):
        return None
    return sum(snapshot[key] for key in keys)
