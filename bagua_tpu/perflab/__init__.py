"""Simulated-fleet performance lab: modeled perf evidence without a chip.

A *model* of the step whose every input is independently proven: a test
oracle for bytes and counts, never a device metric (those come from a run on
the chip).  Two halves:

* **Modeled step-time engine** (:mod:`~bagua_tpu.perflab.engine`): trace the
  real sharded step over abstract shapes (the static verifier's trace,
  PR 11), take the CollectiveIR's exact per-leg wire bytes (census-proved
  against the planner's analytic models), price each leg through the
  planner's fitted α–β :class:`~bagua_tpu.service.planner.CostModel`, count
  the traced matmul/conv FLOPs for the compute span, and compose them with
  an explicit overlap-window assumption into a deterministic
  ``modeled_step_ms`` / ``modeled_goodput`` per algorithm × wire precision ×
  overlap cell (``ci/bench_modeled.py`` → ``BENCH_MODELED.json``).

* **Fleet simulator** (:mod:`~bagua_tpu.perflab.fleetsim`): a discrete-event
  simulation of N gangs of modeled step clocks with injectable stragglers,
  bandwidth collapse, preemption and KV flaps, driving the *real* host-side
  machinery — :class:`~bagua_tpu.observability.aggregate.GangAggregator`
  pushes, straggler scoring, flight-recorder digests, breaker/retry paths —
  against a live rendezvous service, entirely on CPU.

The shared ICI/DCN topology assumptions live in
:mod:`~bagua_tpu.perflab.topology`; ``ci/scaling_projection.py`` imports
them so the repo has exactly one α–β/topology model, not two diverging
copies.
"""

from bagua_tpu.perflab.compute import compute_time_s, flops_census
from bagua_tpu.perflab.costbridge import (
    LEG_FOR_PRIMITIVE,
    PricedProgram,
    census_wire_bytes,
    price_program,
)
from bagua_tpu.perflab.engine import (
    ModeledCell,
    model_step_cell,
    pallas_kernel_basis,
)
from bagua_tpu.perflab.fleetsim import (
    BandwidthCollapse,
    FleetConfig,
    FlakyClient,
    KVFlap,
    Preemption,
    Straggler,
    run_fleet,
)
from bagua_tpu.perflab.topology import (
    DEFAULT_TOPOLOGY,
    TopologyAssumptions,
    t_axis_collective,
    t_collective,
    torus_dims,
)

__all__ = [
    "BandwidthCollapse",
    "DEFAULT_TOPOLOGY",
    "FleetConfig",
    "FlakyClient",
    "KVFlap",
    "LEG_FOR_PRIMITIVE",
    "ModeledCell",
    "Preemption",
    "PricedProgram",
    "Straggler",
    "TopologyAssumptions",
    "census_wire_bytes",
    "compute_time_s",
    "flops_census",
    "model_step_cell",
    "pallas_kernel_basis",
    "price_program",
    "run_fleet",
    "t_axis_collective",
    "t_collective",
    "torus_dims",
]
