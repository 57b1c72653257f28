"""Seconds JAX spent tracing and lowering inside the build and the first
dispatch of the step variants built before the window (the events charged
to ``bagua_host/step/build`` and the cold ``bagua_host/step/dispatch``); the
lowering for the step's text, which only a traced run makes, is left out."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "step_trace")
