"""What the set-up readers take from the program's record of cold events:
the partition of everything that ended before the window began, in seconds
by class.  The program says where that is: ``since`` of the engine's host
counters is the instant ``Run.window`` reset them.  A program without the
record, or counters without ``since``, has no partition: every reader then
gives None and its metric is left out of the line."""

#: the classes that share no instant and add up to what the program can name
CLASSES = ("import", "init", "step_trace", "step_compile", "step_text", "other_programs")


def partition(context):
    since = context["counters"]["host_overhead"].get("since")
    if since is None:
        return None
    try:
        from bagua_tpu.observability.cold_start import setup_snapshot
    except ImportError:
        return None
    return setup_snapshot(until=since)


def seconds(context, key: str):
    found = partition(context)
    return found[key] if found else None
