"""Share of this run's ``setup_s`` in the classes the program names (import,
init, the step's tracing, its compile, its text, other programs): the
instrument's own health.  The rest is the interpreter's start, ``import
jax``, the runtime's start, and the caller's own host work and waiting."""

from benchmark.setup_anatomy import CLASSES, partition


def read(context):
    found = partition(context)
    if not found:
        return None
    return 100.0 * sum(found[key] for key in CLASSES) / context["end_to_end"]["setup_s"]
