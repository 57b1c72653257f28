"""Milliseconds per captured step that device 0 spent running operations of
the forward pass again inside the backward pass (the partition's ``recompute``
class of the program's summary: what ``jax.checkpoint`` rebuilds): what
``ouro-2.6b``'s memory plan costs.  A program whose summary has no such class,
or a run without a device trace, gives None."""

from benchmark.step_summary import partition_ms


def read(context):
    return partition_ms(context, "recompute")
