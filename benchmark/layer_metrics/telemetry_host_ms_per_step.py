"""Host milliseconds per step of the window in the tracing's own Python: the
hub after the dispatch (``telemetry``), the flight recorder's replay and
retire (``flight``) and the health monitor's detector (``health``), from the
engine's counters.  ``flight`` lies inside ``dispatch``, so this metric shares
it with ``host_ms_per_step``; the other two are a part of
``control_host_ms_per_step``.  None without a hub."""


def read(context):
    snapshot = context["counters"]["host_overhead"]
    if "completions" not in snapshot:
        return None
    return sum(snapshot.get(f"{key}_ms_per_step", 0.0) for key in ("telemetry", "flight", "health"))
