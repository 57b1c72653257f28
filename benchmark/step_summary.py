"""What the per-layer readers take from the program's own reduction of the
traced run's capture: ``Trainer`` reduces the capture at the end of the
``fit`` call that held it (``trace_analysis.summarize_capture``) and the
module keeps the result, because ``run.py`` has deleted the capture and
closed the trainer by the time the readers run.  A program without that
reducer, or a run without a device trace, has no summary: every reader then
gives None and its metric is left out of the line."""


def summary(context):
    if not context["trace"]:
        return None
    from bagua_tpu.observability import trace_analysis

    last = getattr(trace_analysis, "last_summary", None)
    return last() if last else None


def partition_ms(context, phase: str):
    """Milliseconds per captured step of device 0 in one class of the
    step's partition."""
    found = summary(context)
    return found["partition_ms"].get(phase) if found else None


def exchange(context, key: str):
    found = summary(context)
    return found["exchange"].get(key) if found else None
