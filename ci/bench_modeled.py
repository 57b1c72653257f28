#!/usr/bin/env python
"""Modeled step-time/goodput sweep (committed as BENCH_MODELED.json).

A *model* of the step, every input of which is independently proven or
explicitly stated; a test oracle for bytes and counts, never a device
metric.  For each registered algorithm x wire precision {f32, int8, int4} x
overlap {off, on} on the standard 8-device CPU-sim mesh, the perf lab
(:mod:`bagua_tpu.perflab`) traces the engine's real sharded step over
abstract shapes (no dispatch), prices the CollectiveIR's exact per-leg wire
bytes through the planner's fitted α–β cost model, counts the traced
matmul FLOPs for the compute span, and composes them under a stated
overlap-window assumption into ``modeled_step_ms`` / ``modeled_goodput``.

Hard per-row invariant: the priced wire bytes equal the IR census bytes
**exactly** (both walk the verifier's branch-deduped groups), and every
cell the static verifier passes must price to a nonzero step time.

Cell statuses mirror ``ci/static_verify.py``: ``pass``/``fail`` (the
verifier ran inside the cell), ``skipped`` (no ``wire_precision`` knob),
``fenced`` (engine refuses the combination at construction).

``--check`` re-models the sweep and gates it against the committed
artifact: any status flip, any wire-byte drift (exact), or a
``modeled_step_ms`` drift beyond 2% fails CI — that is the modeled perf
regression gate.  ``--quick`` restricts to the modeled algorithms
(gradient_allreduce, zero), the cells whose flight programs are fully
certified.

Usage::

    python ci/bench_modeled.py [--out BENCH_MODELED.json] [--check] [--quick]
"""

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# The artifact must be byte-reproducible no matter who launches this script:
# perf_audit's --wire lanes setdefault BAGUA_QR_BLOCK=128 in their process,
# and that leaks into our env when the check lane shells out to us — a
# different block size changes the quantized rings' padding/sidecar bytes
# and the exact-byte regression gate would trip on environment, not code.
os.environ["BAGUA_QR_BLOCK"] = "4096"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms import GlobalAlgorithmRegistry, build_algorithm  # noqa: E402
from bagua_tpu.ddp import DistributedDataParallel  # noqa: E402
from bagua_tpu.models.mlp import init_mlp, mse_loss  # noqa: E402
from bagua_tpu.observability.goodput import (  # noqa: E402
    PEAK_FLOPS_PER_CHIP,
    model_flops_per_sample,
)
from bagua_tpu.perflab import (  # noqa: E402
    DEFAULT_TOPOLOGY,
    model_step_cell,
    t_collective,
)
from bagua_tpu.service.planner import CostModel, WireSample  # noqa: E402

LAYERS = [64, 128, 128, 64]
BUCKET_BYTES = 1 << 12
WIRES = ("f32", "int8", "int4")
WIRE_KNOB_ALGOS = ("gradient_allreduce", "zero")
#: named-mesh sweep: the same modeled algorithms, re-traced on 2-D meshes
#: so BENCH_MODELED.json carries dp×tp / dp×fsdp cells keyed by mesh shape
MESH_SPECS = ({"dp": 4, "tp": 2}, {"dp": 4, "fsdp": 2})
MESH_WIRES = ("f32", "int8")
CHIP = "TPU v5 lite"  # device_kind, the key of goodput.PEAK_FLOPS_PER_CHIP
MFU_ASSUMED = 0.3
FIXTURE = os.path.join(REPO, "ci", "fixtures", "vgg16_bucket_spans.json")
#: --check tolerance on modeled_step_ms (bytes and statuses are exact)
STEP_MS_RTOL = 0.02


def make_batch():
    rng = np.random.RandomState(0)
    return (
        jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32)),
        jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32)),
    )


def build_ddp(group, name, wire, overlap):
    kwargs = {} if wire == "f32" else {"wire_precision": wire}
    algo = build_algorithm(name, lr=0.1, **kwargs)
    return DistributedDataParallel(
        mse_loss,
        optax.sgd(0.1, momentum=0.9),
        algo,
        process_group=group,
        bucket_size_bytes=BUCKET_BYTES,
        overlap=overlap,
    )


def fit_cost_model(intra_size: int):
    """The planner's α–β model fitted from the committed vgg16 device-trace
    fixture; legs with no recorded spans take the planner's priors.  The
    fit is deterministic, so the whole artifact is."""
    with open(FIXTURE) as f:
        fix = json.load(f)
    samples = [
        WireSample(
            nbytes=float(s["nbytes"]),
            seconds=float(s["seconds"]),
            leg=str(s.get("leg", "flat")),
            hidden_frac=s.get("hidden_frac"),
        )
        for s in fix.get("wire_samples", [])
    ]
    return CostModel.from_samples(samples, intra_size=intra_size), fix


def mesh_key(shape):
    """Stable row key for one mesh shape: ``inter2xintra4``, ``dp4xtp2``."""
    return "x".join(f"{k}{int(v)}" for k, v in shape.items())


def sweep_cell(group, params, batch, cost_model, name, wire, overlap):
    row = {
        "algo": name,
        "wire": wire,
        "overlap": overlap,
        "mesh_key": mesh_key(dict(group.mesh.shape)),
    }
    if wire != "f32" and name not in WIRE_KNOB_ALGOS:
        row["status"] = "skipped"
        row["reason"] = "algorithm has no wire_precision knob"
        return row
    try:
        ddp = build_ddp(group, name, wire, overlap)
    except ValueError as e:
        row["status"] = "fenced"
        row["reason"] = str(e)
        return row
    try:
        state = ddp.init(params)
        cell = model_step_cell(
            ddp, state, batch, cost_model,
            topology=DEFAULT_TOPOLOGY, chip=CHIP, mfu=MFU_ASSUMED, wire=wire,
        )
    finally:
        ddp.shutdown()
    cell_json = cell.to_json()
    # the row key stays the registry name; the engine's scope label (canonical
    # algo, "" for zero-collective programs) is provenance, not identity
    cell_json["engine_algo"] = cell_json.pop("algo")
    row.update(cell_json)
    row["status"] = "pass" if cell.verified else "fail"
    # the lane's hard invariants — a modeled number is only admissible when
    # its byte provenance is the proven census
    if cell.modeled_wire_bytes != cell.census_wire_bytes:
        row["status"] = "fail"
        row.setdefault("findings", []).append(
            f"priced bytes {cell.modeled_wire_bytes} != census "
            f"{cell.census_wire_bytes}"
        )
    if row["status"] == "pass" and not row["modeled_step_ms"] > 0:
        row["status"] = "fail"
        row.setdefault("findings", []).append("modeled_step_ms is zero")
    return row


def vgg16_projection(cost_model, fixture, topo=DEFAULT_TOPOLOGY,
                     local_batch=32, n_chips=8):
    """The bench harness's headline metrics, modeled: VGG16 DP img/s/chip
    and 1→8 weak-scaling efficiency, from the fixture's parameter census +
    the analytic FLOPs model + the shared topology assumptions."""
    grad_bytes = sum(
        int(d["num_elements"]) * 4 for d in fixture.get("declarations", [])
    )
    flops_per_step = model_flops_per_sample("vgg16") * local_batch
    compute_s = flops_per_step / (PEAK_FLOPS_PER_CHIP[CHIP] * MFU_ASSUMED)
    wire_s = t_collective("allreduce", grad_bytes, n_chips, topo)
    exposed_s = max(0.0, wire_s - topo.overlap_window_frac * compute_s)
    t_n = compute_s + exposed_s
    return {
        "model": "vgg16",
        "algo": "gradient_allreduce",
        "local_batch": local_batch,
        "n_chips": n_chips,
        "grad_bytes": grad_bytes,
        "flops_per_step_per_chip": flops_per_step,
        "compute_ms": round(compute_s * 1e3, 6),
        "wire_ms": round(wire_s * 1e3, 6),
        "exposed_wire_ms": round(exposed_s * 1e3, 6),
        "modeled_step_ms": round(t_n * 1e3, 6),
        "modeled_img_per_s_per_chip": round(local_batch / t_n, 3),
        # weak scaling: 1 chip has no wire term at all
        "modeled_scaling_efficiency_8": round(compute_s / t_n, 6),
        "modeled_scaling_efficiency_8_no_overlap": round(
            compute_s / (compute_s + wire_s), 6
        ),
    }


def run_sweep(args):
    group = bagua_tpu.init_process_group(intra_size=4)
    cost_model, fixture = fit_cost_model(intra_size=4)
    params = init_mlp(jax.random.PRNGKey(0), LAYERS)
    batch = make_batch()

    names = list(GlobalAlgorithmRegistry.keys())
    if args.quick:
        names = [n for n in names if n in WIRE_KNOB_ALGOS]
    if args.algo is not None:
        names = [n for n in names if n == args.algo]

    rows = []
    for name in names:
        for wire in WIRES:
            for overlap in (False, True):
                row = sweep_cell(
                    group, params, batch, cost_model, name, wire, overlap
                )
                rows.append(row)
                extra = ""
                if "modeled_step_ms" in row:
                    extra = (f" {row['modeled_step_ms']:.3f} ms, "
                             f"{row['modeled_wire_bytes']} B wire")
                print(
                    f"[bench-modeled] {name:28s} wire={wire:4s} "
                    f"overlap={int(overlap)} -> {row['status']}{extra}",
                    file=sys.stderr,
                )

    # Named-mesh cells: the same trace → census → α–β pipeline, re-run on
    # 2-D meshes.  Only the fully-modeled algorithms ride here (the mesh
    # engine certifies exactly those), and every row carries its mesh shape
    # + exchange axes so the check lane gates dp×tp and dp×fsdp cells
    # independently of the legacy 1-D rows.
    mesh_names = [n for n in names if n in WIRE_KNOB_ALGOS]
    for spec_axes in MESH_SPECS:
        mesh_group = bagua_tpu.new_group(
            mesh_spec=bagua_tpu.MeshSpec(spec_axes)
        )
        mkey = mesh_key(spec_axes)
        for name in mesh_names:
            for wire in MESH_WIRES:
                for overlap in (False, True):
                    row = sweep_cell(
                        mesh_group, params, batch, cost_model,
                        name, wire, overlap,
                    )
                    rows.append(row)
                    extra = ""
                    if "modeled_step_ms" in row:
                        extra = (f" {row['modeled_step_ms']:.3f} ms, "
                                 f"{row['modeled_wire_bytes']} B wire")
                    print(
                        f"[bench-modeled] {name:28s} wire={wire:4s} "
                        f"overlap={int(overlap)} mesh={mkey} "
                        f"-> {row['status']}{extra}",
                        file=sys.stderr,
                    )

    summary = {
        s: sum(1 for r in rows if r["status"] == s)
        for s in ("pass", "fail", "skipped", "fenced")
    }
    report = {
        "schema": 1,
        "generated_by": "ci/bench_modeled.py",
        "mesh": dict(group.mesh.shape),
        "meshes": [dict(group.mesh.shape)] + [dict(s) for s in MESH_SPECS],
        "model": {"layers": LAYERS, "bucket_size_bytes": BUCKET_BYTES},
        "assumptions": {
            "chip": CHIP,
            "peak_flops_per_chip": PEAK_FLOPS_PER_CHIP[CHIP],
            "mfu": MFU_ASSUMED,
            "topology": DEFAULT_TOPOLOGY.describe(),
            "cost_model": cost_model.describe(),
            "cost_model_source": os.path.relpath(FIXTURE, REPO),
            "provenance": {
                "wire_bytes": "proved: CollectiveIR census == planner "
                              "analytic models (check_wire_exactness)",
                "alpha_beta": "fitted: recorded device-trace spans, "
                              "planner priors for unsampled legs",
                "compute": "stated: traced matmul/conv FLOPs at assumed "
                           "MFU of chip peak",
                "overlap": "stated: overlap_window_frac of the compute "
                           "span can hide wire time",
            },
        },
        "summary": summary,
        "rows": rows,
        "vgg16_projection": vgg16_projection(cost_model, fixture),
    }
    return report


def check_against(report, committed_path):
    """The regression gate: fresh model vs committed artifact."""
    try:
        with open(committed_path) as f:
            committed = json.load(f)
    except OSError as e:
        return [f"committed artifact unreadable: {e}"]
    # mesh_key joined into the row identity: dp×tp / dp×fsdp cells gate
    # independently of the legacy rows.  Rows of pre-mesh artifacts carry
    # no mesh_key and default to the legacy 1-D shape, so fresh legacy rows
    # still match them while fresh mesh rows stay additive.
    def row_key(r):
        return (
            r.get("mesh_key", "inter2xintra4"),
            r["algo"], r["wire"], r["overlap"],
        )

    old = {row_key(r): r for r in committed.get("rows", [])}
    problems = []
    for r in report["rows"]:
        key = row_key(r)
        ref = old.get(key)
        if ref is None:
            continue  # new cell: additive, not a regression
        if r["status"] != ref["status"]:
            problems.append(
                f"{key}: status {ref['status']} -> {r['status']}"
            )
            continue
        if r["status"] != "pass":
            continue
        if r["modeled_wire_bytes"] != ref["modeled_wire_bytes"]:
            problems.append(
                f"{key}: wire bytes {ref['modeled_wire_bytes']} -> "
                f"{r['modeled_wire_bytes']} (must be exact)"
            )
        ref_ms = ref["modeled_step_ms"]
        if abs(r["modeled_step_ms"] - ref_ms) > STEP_MS_RTOL * ref_ms:
            problems.append(
                f"{key}: modeled_step_ms {ref_ms} -> "
                f"{r['modeled_step_ms']} (> {STEP_MS_RTOL:.0%} drift)"
            )
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out",
        default=os.path.join(REPO, "BENCH_MODELED.json"),
        help="where to write the modeled sweep (default: repo root)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="gate against the committed artifact instead of rewriting it",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="restrict to the modeled algorithms (gradient_allreduce, zero)",
    )
    ap.add_argument(
        "--algo", default=None, help="restrict the sweep to one algorithm"
    )
    args = ap.parse_args(argv)

    report = run_sweep(args)
    summary = report["summary"]

    if args.check:
        problems = check_against(report, args.out)
        for p in problems:
            print(f"[bench-modeled] REGRESSION: {p}", file=sys.stderr)
        if summary["fail"] or problems:
            print(
                f"[bench-modeled] check failed: {summary['fail']} cell "
                f"failure(s), {len(problems)} regression(s)",
                file=sys.stderr,
            )
            return 1
        print(
            f"[bench-modeled] check passed vs {args.out}: {summary}",
            file=sys.stderr,
        )
        return 0

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=False)
        f.write("\n")
    print(f"[bench-modeled] wrote {args.out}: {summary}", file=sys.stderr)
    if summary["fail"]:
        print(f"[bench-modeled] {summary['fail']} failure(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
