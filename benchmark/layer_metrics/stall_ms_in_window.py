"""Milliseconds of the window lost to stalls as the program counts them: the
excess over the recent median of every completion interval over twice that
median (``completions.stall_ms``; each is a ``stall`` event that names the
host's phase).  None without a hub."""


def read(context):
    completions = context["counters"]["host_overhead"].get("completions")
    return completions["stall_ms"] if completions else None
