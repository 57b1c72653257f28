#!/usr/bin/env python
"""Static collective-program verification sweep (committed as STATIC_VERIFY.json).

Runs the trace-time verifier (``bagua_tpu/analysis/``) over every registered
algorithm x wire precision {f32, int8, int4} x overlap {off, on} on the
standard 8-device CPU-sim mesh (2 inter x 4 intra), with no device dispatch:
each cell traces the engine's sharded step over abstract shapes, extracts the
collective IR, and runs the four checkers (rank invariance, wire-byte
exactness, plan conformance, static/dynamic flight-program agreement).

Cell statuses:

* ``pass`` / ``fail`` — the verifier ran; ``fail`` carries the findings.
* ``skipped`` — the combination is not expressible (the algorithm has no
  ``wire_precision`` knob).
* ``fenced`` — the engine itself rejects the combination at construction
  (e.g. int4 error-feedback state vs overlap); the rejection message is the
  row's evidence.  A fence is a *successful* outcome: the verifier never
  needs to see a program the engine refuses to build.

For the modeled algorithms (``gradient_allreduce``, ``zero``) the sweep
additionally runs one **live** step under ``BAGUA_STATIC_VERIFY=strict`` with
the flight recorder attached, and asserts the statically predicted flight
program equals the recorder's post-dispatch capture record-for-record — the
static/dynamic mutual certification the CI acceptance requires.

Exit status is nonzero on any ``fail`` or live-capture mismatch.

Usage::

    python ci/static_verify.py [--out STATIC_VERIFY.json]
"""

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["BAGUA_STATIC_VERIFY"] = "strict"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402,F401
import numpy as np  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu.algorithms import GlobalAlgorithmRegistry, build_algorithm  # noqa: E402
from bagua_tpu.analysis import (  # noqa: E402
    MODELED_ALGOS,
    check_static_dynamic,
    verify_step_program,
)
from bagua_tpu.ddp import DistributedDataParallel  # noqa: E402
from bagua_tpu.models.mlp import init_mlp, mse_loss  # noqa: E402
from bagua_tpu.observability.flight_recorder import FlightRecorder  # noqa: E402
from bagua_tpu.observability.telemetry import Telemetry  # noqa: E402

LAYERS = [64, 128, 128, 64]
BUCKET_BYTES = 1 << 12
WIRES = ("f32", "int8", "int4")
#: algorithms exposing the shared wire_precision knob (_precision.py mixin)
WIRE_KNOB_ALGOS = ("gradient_allreduce", "zero")
#: modeled algorithms that get the live static-vs-capture certification step
LIVE_ALGOS = MODELED_ALGOS


def make_batch():
    rng = np.random.RandomState(0)
    return (
        jnp.asarray(rng.randn(32, LAYERS[0]).astype(np.float32)),
        jnp.asarray(rng.randn(32, LAYERS[-1]).astype(np.float32)),
    )


def build_ddp(group, name, wire, overlap, telemetry=None):
    kwargs = {} if wire == "f32" else {"wire_precision": wire}
    algo = build_algorithm(name, lr=0.1, **kwargs)
    return DistributedDataParallel(
        mse_loss,
        optax.sgd(0.1, momentum=0.9),
        algo,
        process_group=group,
        bucket_size_bytes=BUCKET_BYTES,
        overlap=overlap,
        telemetry=telemetry,
    )


def sweep_cell(group, params, batch, name, wire, overlap):
    row = {
        "algo": name,
        "wire": wire,
        "overlap": overlap,
        "modeled": name in MODELED_ALGOS,
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
        variant = ddp.impl.step_variant(0)
        report = verify_step_program(ddp, state, batch, variant=variant)
        row["status"] = "pass" if report.ok else "fail"
        row["variant"] = str(variant)
        row["num_collectives"] = report.num_collectives
        row["findings"] = [f.to_json() for f in report.findings]
        row["wire_table"] = report.wire_table
        row["predicted_records"] = len(report.predicted)
        row["captured_records"] = len(report.captured)
    finally:
        ddp.shutdown()
    return row


def live_certify(group, params, batch, name):
    """One real dispatched step under strict mode: the pre-dispatch gate
    verifies the trace, the flight recorder captures the live program, and
    the engine's crosscheck (plus this function's explicit re-comparison)
    proves prediction == capture record-for-record."""
    tel = Telemetry(flight=FlightRecorder(capacity=256, rank=0, world_size=1))
    ddp = build_ddp(group, name, "f32", False, telemetry=tel)
    try:
        state = ddp.init(params)
        state, losses = ddp.train_step(state, batch)
        jax.block_until_ready(losses)
        variant = ddp.impl.step_variant(0)
        captured = ddp.flight_program(variant)
        predicted = ddp.predicted_program(variant)
        if not captured or not predicted:
            return {
                "algo": name,
                "match": False,
                "reason": "missing flight program or prediction",
            }
        findings = check_static_dynamic(predicted, captured)
        errors = [str(f) for f in findings if f.severity == "error"]
        return {
            "algo": name,
            "variant": str(variant),
            "records": len(captured),
            "match": not errors,
            "mismatches": errors,
        }
    finally:
        ddp.shutdown()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out",
        default=os.path.join(REPO, "STATIC_VERIFY.json"),
        help="where to write the sweep report (default: repo root)",
    )
    ap.add_argument(
        "--algo", default=None, help="restrict the sweep to one algorithm"
    )
    args = ap.parse_args(argv)

    group = bagua_tpu.init_process_group(intra_size=4)
    params = init_mlp(jax.random.PRNGKey(0), LAYERS)
    batch = make_batch()

    names = GlobalAlgorithmRegistry.keys()
    if args.algo is not None:
        names = [n for n in names if n == args.algo]

    rows = []
    for name in names:
        for wire in WIRES:
            for overlap in (False, True):
                row = sweep_cell(group, params, batch, name, wire, overlap)
                rows.append(row)
                print(
                    f"[static-verify] {name:28s} wire={wire:4s} "
                    f"overlap={int(overlap)} -> {row['status']}"
                    + (
                        f" ({row['num_collectives']} collectives)"
                        if "num_collectives" in row
                        else ""
                    ),
                    file=sys.stderr,
                )

    live = []
    for name in LIVE_ALGOS:
        if args.algo is not None and name != args.algo:
            continue
        res = live_certify(group, params, batch, name)
        live.append(res)
        print(
            f"[static-verify] live {name}: "
            + ("match" if res["match"] else f"MISMATCH {res}"),
            file=sys.stderr,
        )

    summary = {
        s: sum(1 for r in rows if r["status"] == s)
        for s in ("pass", "fail", "skipped", "fenced")
    }
    summary["live_match"] = sum(1 for r in live if r["match"])
    summary["live_mismatch"] = sum(1 for r in live if not r["match"])
    report = {
        "schema": 1,
        "generated_by": "ci/static_verify.py",
        "mesh": dict(group.mesh.shape),
        "model": {"layers": LAYERS, "bucket_size_bytes": BUCKET_BYTES},
        "modeled_algos": list(MODELED_ALGOS),
        "summary": summary,
        "rows": rows,
        "live_capture": live,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=False)
        f.write("\n")
    print(f"[static-verify] wrote {args.out}: {summary}", file=sys.stderr)

    failed = summary["fail"] + summary["live_mismatch"]
    if failed:
        print(f"[static-verify] {failed} failure(s)", file=sys.stderr)
        return 1
    print("[static-verify] all verified", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
