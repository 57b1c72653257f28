"""Backend compilations (cache loads included) between the window's start
and its end, counted by the benchmark's ``jax.monitoring`` listener.
Expected 0: every shape is warmed in set-up."""


def read(context):
    return context["counters"]["compiles_in_window"]
