"""Peak device memory in use, ``memory_stats()["peak_bytes_in_use"]`` read
after the window and before the reference runs, the largest over the cell's
devices.  It is the room that is left for a larger batch."""


def read(context):
    peak = context["device"]["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
