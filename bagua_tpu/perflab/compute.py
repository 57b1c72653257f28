"""Traced compute spans: FLOP census of a step jaxpr.

The modeled step time needs a compute term from the *same trace* that
yields the CollectiveIR, so the provenance chain stays single-source: one
``jax.make_jaxpr`` of the engine's sharded step gives both the wire program
(collectives with exact ring-model bytes) and the compute program (every
``dot_general`` / ``conv_general_dilated`` with its local, per-shard
shapes — the walker descends into the ``shard_map`` sub-jaxpr, so the
counted shapes are per-chip).

Control flow: a ``scan`` body is multiplied by its trip count, sibling
``cond`` branches contribute their maximum (only one executes), a
``while`` body is counted once (trip count is unknowable statically — the
engine's step programs carry no compute-bearing whiles today), and a
``custom_jvp``/``custom_vjp`` call counts only its primal ``call_jaxpr``
(the fwd/bwd thunks shadow the same math).

The census is FLOPs, not seconds; :func:`compute_time_s` turns it into a
compute span under an explicit peak-FLOPs × assumed-MFU model (both
recorded in BENCH_MODELED.json's assumptions block).
"""

from typing import Dict

from jax._src import core as jcore

from bagua_tpu.observability.goodput import PEAK_FLOPS_PER_CHIP

__all__ = ["compute_time_s", "flops_census"]


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_flops(eqn) -> float:
    """2·batch·M·N·K for one ``dot_general``."""
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = _prod(lhs.shape[i] for i in lb)
    contract = _prod(lhs.shape[i] for i in lc)
    lhs_free = _prod(
        d for i, d in enumerate(lhs.shape) if i not in lc and i not in lb
    )
    rhs_free = _prod(
        d for i, d in enumerate(rhs.shape) if i not in rc and i not in rb
    )
    return 2.0 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn) -> float:
    """2 · output elements · reduction depth for one conv."""
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    try:
        out_ch_dim = eqn.params["dimension_numbers"].rhs_spec[0]
        out_ch = int(rhs.shape[out_ch_dim])
    except Exception:  # defensive: dimension-number layout drift
        out_ch = int(max(rhs.shape))
    reduction = _prod(rhs.shape) / max(1, out_ch)
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    return 2.0 * _prod(out.shape) * reduction / groups


def _closed(j):
    return j.jaxpr if isinstance(j, jcore.ClosedJaxpr) else j


def _walk(jaxpr) -> Dict[str, float]:
    tot = {"flops": 0.0, "dot_flops": 0.0, "conv_flops": 0.0,
           "n_dots": 0, "n_convs": 0}

    def add(sub: Dict[str, float], scale: float = 1.0):
        tot["flops"] += sub["flops"] * scale
        tot["dot_flops"] += sub["dot_flops"] * scale
        tot["conv_flops"] += sub["conv_flops"] * scale
        tot["n_dots"] += sub["n_dots"]
        tot["n_convs"] += sub["n_convs"]

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            f = _dot_flops(eqn)
            tot["flops"] += f
            tot["dot_flops"] += f
            tot["n_dots"] += 1
            continue
        if name == "conv_general_dilated":
            f = _conv_flops(eqn)
            tot["flops"] += f
            tot["conv_flops"] += f
            tot["n_convs"] += 1
            continue
        if name == "cond":
            branches = [
                _walk(_closed(b)) for b in eqn.params.get("branches", ())
            ]
            if branches:
                add(max(branches, key=lambda s: s["flops"]))
            continue
        if name == "scan":
            length = int(eqn.params.get("length", 1) or 1)
            add(_walk(_closed(eqn.params["jaxpr"])), scale=length)
            continue
        if "custom_jvp" in name or "custom_vjp" in name:
            cj = eqn.params.get("call_jaxpr")
            if cj is not None:
                add(_walk(_closed(cj)))
            continue
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for w in vs:
                if isinstance(w, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                    add(_walk(_closed(w)))
    return tot


def flops_census(closed_jaxpr) -> Dict[str, float]:
    """Per-chip matmul/conv FLOPs of one traced step program."""
    out = _walk(_closed(closed_jaxpr))
    out["n_dots"] = int(out["n_dots"])
    out["n_convs"] = int(out["n_convs"])
    return out


def compute_time_s(flops: float, chip: str = "TPU v5 lite", mfu: float = 0.3) -> float:
    """Modeled compute span: traced FLOPs at ``mfu`` of the chip's peak.

    ``mfu`` is an explicit assumption (BENCH_MODELED.json records it) — the
    modeled *trend* across algorithms/precisions is exact in the wire term
    and shares one compute scale factor, so ranking is insensitive to it.
    """
    peak = PEAK_FLOPS_PER_CHIP[chip]
    denom = peak * mfu
    if denom <= 0:
        raise ValueError(f"non-positive effective peak: {chip=} {mfu=}")
    return float(flops) / denom
