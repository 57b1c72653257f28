"""Modeled step-time engine: one trace → census bytes → α–β legs → ms.

:func:`model_step_cell` is the per-configuration worker behind
``ci/bench_modeled.py``.  It traces a live engine's sharded step over
abstract shapes (the static verifier's trace — nothing dispatches), runs
the four checkers over the extracted CollectiveIR, prices the IR's
branch-deduped wire bytes through the planner's per-leg cost model
(:mod:`~bagua_tpu.perflab.costbridge`), counts the traced matmul/conv
FLOPs (:mod:`~bagua_tpu.perflab.compute`) and composes the two spans under
the explicit overlap-window assumption of
:class:`~bagua_tpu.perflab.topology.TopologyAssumptions`:

    ``exposed = max(0, wire − window·compute)``   (overlap on)
    ``exposed = wire``                            (overlap off)
    ``modeled_step = compute + exposed``

Every number in the chain is either *proved* (bytes: ``check_wire_exactness``
holds them equal to the planner's analytic models), *fitted* (α–β legs from
recorded spans, priors when a leg has none) or *stated* (MFU, overlap
window, chip peak) — BENCH_MODELED.json records which is which.

Pallas honesty: cells whose wire program rides evidence-gated Pallas
kernels are marked via :func:`pallas_kernel_basis` — on this container the
evidence (PALLAS_TPU.json) is interpret-mode CPU, so such rows carry
``kernel_basis="modeled-jnp-fallback"`` rather than being silently priced
as if the fused kernels had chip evidence.
"""

import dataclasses
import json
import os
from typing import Dict, List, Optional

import jax

from bagua_tpu.analysis.checks import WireModelConfig
from bagua_tpu.analysis.collective_ir import extract_collective_ir
from bagua_tpu.analysis.verify import _abstract, verify_collective_program
from bagua_tpu.observability.flight_recorder import capture_program
from bagua_tpu.perflab.compute import compute_time_s, flops_census
from bagua_tpu.perflab.costbridge import census_wire_bytes, price_program
from bagua_tpu.perflab.topology import DEFAULT_TOPOLOGY, TopologyAssumptions
from bagua_tpu.service.planner import CostModel

__all__ = [
    "ModeledCell",
    "model_step_cell",
    "pallas_kernel_basis",
]

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass
class ModeledCell:
    """One algorithm × wire × overlap configuration, modeled."""

    algo: str
    wire: str
    overlap: bool
    verified: bool                  #: the four checkers passed on this trace
    modeled_step_ms: float
    modeled_samples_per_s: float    #: global batch / modeled step
    modeled_goodput_frac: float     #: compute span / modeled step
    modeled_mfu: float              #: traced FLOPs / (modeled step · peak)
    compute_ms: float
    wire_ms: float
    exposed_wire_ms: float
    modeled_wire_bytes: int         #: priced bytes (== census, asserted)
    census_wire_bytes: int          #: branch-deduped IR bytes
    flops_per_step: float
    num_collectives: int
    legs_used: List[str]
    leg_breakdown: Dict[str, Dict]
    kernel_basis: Dict
    findings: List[str]
    #: the engine's mesh shape (``{"inter": 2, "intra": 4}`` or
    #: ``{"dp": 4, "tp": 2}``) — the cell key that lets BENCH_MODELED.json
    #: hold dp×tp / dp×fsdp cells alongside the 1-D rows
    mesh: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: axes the cell's gradient exchange rode (provenance for per-axis legs)
    exchange_axes: List[str] = dataclasses.field(default_factory=list)

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        for k in ("modeled_step_ms", "compute_ms", "wire_ms", "exposed_wire_ms"):
            d[k] = round(d[k], 6)
        d["modeled_samples_per_s"] = round(d["modeled_samples_per_s"], 3)
        d["modeled_goodput_frac"] = round(d["modeled_goodput_frac"], 6)
        d["modeled_mfu"] = round(d["modeled_mfu"], 6)
        for leg in d["leg_breakdown"].values():
            leg["seconds"] = round(leg["seconds"], 9)
        return d


def pallas_kernel_basis(
    algo: str, wire: str, evidence_path: Optional[str] = None
) -> Dict:
    """How the cell's kernel tier is priced: ``measured-chip`` only when
    PALLAS_TPU.json carries real-chip (non-interpret) evidence for every
    kernel the cell's wire program is gated on; ``modeled-jnp-fallback``
    otherwise (the dispatch layer runs the jnp oracle without evidence, so
    pricing must not assume the fused kernel).  Cells with no gated kernel
    are ``jnp-native``."""
    gated: List[str] = []
    if wire in ("int8", "int4"):
        gated = [f"quantized_ring_hop_{wire}", "decompress_reduce_requantize"]
    elif algo in ("bytegrad", "qadam") or (algo == "zero" and wire != "f32"):
        gated = ["minmax_uint8"]
    if not gated:
        return {"basis": "jnp-native", "gated_kernels": []}
    path = evidence_path or os.path.join(_REPO, "PALLAS_TPU.json")
    backend, interpret, known = "", True, set()
    try:
        with open(path) as f:
            ev = json.load(f)
        backend = str(ev.get("backend", ""))
        interpret = bool(ev.get("interpret", True))
        known = {k.get("kernel") for k in ev.get("kernels", [])}
    except (OSError, ValueError):
        pass
    chip_evidence = (
        backend.startswith("tpu")
        and not interpret
        and all(k in known for k in gated)
    )
    return {
        "basis": "measured-chip" if chip_evidence else "modeled-jnp-fallback",
        "gated_kernels": gated,
        "evidence_backend": backend or None,
    }


def model_step_cell(
    ddp,
    state,
    batch,
    cost_model: CostModel,
    topology: TopologyAssumptions = DEFAULT_TOPOLOGY,
    chip: str = "TPU v5 lite",
    mfu: float = 0.3,
    wire: str = "f32",
) -> ModeledCell:
    """Model one live engine's step from a single abstract-shape trace.

    The caller owns engine construction/teardown (and the fenced/skipped
    taxonomy — an engine that refuses to build never reaches here).
    """
    from bagua_tpu.observability.goodput import PEAK_FLOPS_PER_CHIP

    variant = ddp.impl.step_variant(0)
    cfg = WireModelConfig.from_engine(ddp)
    sharded = ddp._build_sharded(variant)
    with capture_program() as events:
        closed = jax.make_jaxpr(sharded)(_abstract(state), _abstract(batch))
    program = extract_collective_ir(closed, dict(ddp.group.mesh.shape))
    captured = list(ddp._flight_finalize(variant, events))
    report = verify_collective_program(
        program, cfg, captured=captured, variant=variant
    )

    priced = price_program(program, cost_model, cfg)
    census = census_wire_bytes(program, cfg)
    flops = flops_census(closed)
    compute_s = compute_time_s(flops["flops"], chip=chip, mfu=mfu)
    wire_s = priced.total_wire_s
    if ddp.overlap_enabled:
        exposed_s = max(0.0, wire_s - topology.overlap_window_frac * compute_s)
    else:
        exposed_s = wire_s
    step_s = compute_s + exposed_s
    global_batch = int(jax.tree.leaves(batch)[0].shape[0])
    return ModeledCell(
        algo=cfg.algo,
        wire=wire,
        overlap=bool(ddp.overlap_enabled),
        verified=report.ok,
        modeled_step_ms=step_s * 1e3,
        modeled_samples_per_s=global_batch / step_s,
        modeled_goodput_frac=compute_s / step_s,
        modeled_mfu=flops["flops"] / (step_s * PEAK_FLOPS_PER_CHIP[chip]),
        compute_ms=compute_s * 1e3,
        wire_ms=wire_s * 1e3,
        exposed_wire_ms=exposed_s * 1e3,
        modeled_wire_bytes=priced.total_wire_bytes,
        census_wire_bytes=census,
        flops_per_step=flops["flops"],
        num_collectives=len(program.collectives),
        legs_used=priced.legs_used,
        leg_breakdown=priced.by_leg(),
        kernel_basis=pallas_kernel_basis(cfg.algo, wire),
        findings=[str(f) for f in report.errors],
        mesh={k: int(v) for k, v in ddp.group.mesh.shape.items()},
        exchange_axes=list(cfg.exchange_axes),
    )
