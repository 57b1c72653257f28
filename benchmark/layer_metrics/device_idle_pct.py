"""Share of the captured window in which no operation ran on device 0: one
minus the union of its operations' intervals over the span from the first
operation's start to the last one's end."""


def read(context):
    trace = context["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["device0_busy_s"] / trace["device0_window_s"])
