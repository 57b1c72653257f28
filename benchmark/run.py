#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  The platform is pinned to ``tpu`` before first
device use: without a chip, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.  ``--dry-run`` is the only way onto the
CPU, at toy widths, and marks its line as such; its numbers are no device
numbers.  The last line of standard output is the result as one JSON object:
with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics and the breakdown of a captured stretch of the window.
"""

import time

STARTED = time.perf_counter()  # set-up counts from here: imports included

import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="toy widths on the CPU; the line is marked dry_run")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the capture under chiprun_out/trace/")
    return ap.parse_args(argv)


def pin_platform(dry: bool, chips: int):
    """Before first device use.  libtpu is installed wherever this runs, and
    with the platform left open JAX would fall back to the CPU by itself."""
    if dry:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={chips}"
    import jax

    jax.config.update("jax_platforms", "cpu" if dry else "tpu")
    devices = jax.devices()  # raises where there is no chip
    if devices[0].platform != ("cpu" if dry else "tpu"):
        raise SystemExit(f"wrong platform: {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"{len(devices)} devices for a cell of {chips} chips")
    return devices[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, ROOT)
    from benchmark import manifest

    cell = manifest.load_cell(args.workload, dry=args.dry_run)
    devices = pin_platform(args.dry_run, cell.chips)

    import jax

    import bagua_tpu
    from bagua_tpu.env import setup_compile_cache
    from benchmark import check, harness

    if os.path.dirname(os.path.abspath(bagua_tpu.__file__)) != os.path.join(ROOT, "bagua_tpu"):
        raise ImportError(f"bagua_tpu came from {bagua_tpu.__file__}, not from {ROOT}")
    cache_dir = setup_compile_cache()
    if not args.dry_run:
        # on the chip the small programs are cached too.  Not in a dry run: the
        # repo's tests share this cache directory, and entries for programs
        # that differ only in their labels would answer one another
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say = (lambda line: print("DRY RUN " + line, flush=True)) if args.dry_run else (
        lambda line: print(line, flush=True))
    say(f"workload={cell.name} seed={args.seed} platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, "chiprun_out", "trace", f"{cell.name}-seed{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(cell, args.seed, STARTED, devices, trace_dir=trace_dir)
    with harness.closing_run(run):
        run.build()
        run.setup()
        run.window(args.seconds)
        stats = run.window_stats()
        trace = run.trace()
        if trace_dir is not None and not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.free_program()
        after_window = time.perf_counter()
        numbers = run.numbers(run.reference())
        reference_s = time.perf_counter() - after_window

    say(f"setup_s={run.setup_s:.3f} reference_s={reference_s:.3f} (not in setup_s) "
        f"warmup_losses={' '.join(f'{x:.5f}' for x in run.warmup_losses)}")
    window_losses = [loss for _, loss in run.completions if loss is not None]
    say(f"window: dispatched={stats['dispatched']} completed_in_window="
        f"{stats['completed_in_window']} failed={stats['failed']} "
        f"step_ms_median={stats.get('step_ms_median')} step_ms_p95={stats.get('step_ms_p95')} "
        f"step_ms_max={stats.get('step_ms_max')} "
        f"loss_first={window_losses[0] if window_losses else None} "
        f"loss_last={window_losses[-1] if window_losses else None} "
        f"compiles_in_window={run.compiles_in_window}")
    correct, lines = check.verdict(numbers, cell.tolerances)
    for line in lines:
        say(line)
    say("check worst leaves: " + " ".join(
        f"{k}={run.worst_leaves[k]}" for k in ("grad_norm_gap", "update_norm_gap")))
    finite = stats["failed"] == 0 and stats["completed_in_window"] >= 2
    say(f"check window_losses_finite={finite}")

    context = {
        "end_to_end": {
            "samples_per_s_per_chip": stats.get("samples_per_s_per_chip"),
            "step_ms_p95": stats.get("step_ms_p95"),
            "setup_s": run.setup_s,
        },
        "window": stats,
        "counters": {"compiles_in_window": run.compiles_in_window,
                     "host_overhead": run.host_overhead},
        "trace": trace,
        "device": run.device,
        "peaks": None if args.dry_run else manifest.peaks(run.device["kind"]),
        "batch_per_chip": cell.traffic["batch_per_chip"],
        "train_flops_per_sample": cell.adapter.train_flops_per_sample(run.sizes),
    }
    device = dict(run.device)
    result = {
        "correct": bool(correct and finite),
        "attempted": stats["dispatched"],
        "failed": stats["failed"],
        "metrics": harness.metrics_for(
            cell.per_layer if args.trace else cell.end_to_end, context),
        "device": device,
    }
    if trace is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["workload"], result["seed"], result["checks"] = cell.name, args.seed, numbers
    if args.dry_run:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    # and each number compared beside its limit as the last lines on standard error, where the
    # driver's record of a run that is not correct keeps them
    print("\n".join(lines + [f"check window_losses_finite={finite}"]), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
