"""Share of the step module's busy time on device 0 in operations that carry
no ``bagua_step`` label and are no collective: the instrument's own health.
A refactor that drops the scopes shows here."""

from benchmark.step_summary import summary


def read(context):
    found = summary(context)
    if not found or not found["labeled"]:
        return None
    return 100.0 * found["partition_ms"].get("unattributed", 0.0) / found["step_busy_ms"]
