"""Milliseconds per captured step that device 0 spent in operations traced
under ``bagua_step/phase=optimizer`` or ``sharded_update``, from the
program's summary of the capture.  What the compiler fuses into a backward
operation is counted there, not here."""

from benchmark.step_summary import partition_ms


def read(context):
    return partition_ms(context, "optimizer")
