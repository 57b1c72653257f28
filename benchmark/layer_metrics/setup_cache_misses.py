"""Backend compilations before the window that the persistent cache did not
answer and that were written to it: 0 on a warm machine, so the count tells
a cold line from a warm one."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "cache_misses")
