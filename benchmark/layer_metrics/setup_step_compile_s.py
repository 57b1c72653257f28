"""Seconds of the backend's compile, or of the persistent cache's answer in
its place, for the step variants built before the window."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "step_compile")
