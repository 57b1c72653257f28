"""The 95th percentile of the intervals between steps completing in the
window as the *program* saw them (``completions.interval_ms.p95``: its
waiter's stamps), to be held against the harness's ``step_ms_p95`` of the
same run, which its own watcher stamped.  None without a hub."""


def read(context):
    completions = context["counters"]["host_overhead"].get("completions")
    return completions["interval_ms"].get("p95") if completions else None
