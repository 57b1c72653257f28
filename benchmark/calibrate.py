#!/usr/bin/env python3
"""Reads, in one process, the numbers ``correct`` compares over many seeds:
the program's checked steps against the plain reference (sound runs), and
the control's (the reference in the next lower precision, put in the
program's place) against the same reference.  The limits in the
configurations' JSON are set from these readings; the benchmark's own runs
never run this.

    python benchmark/calibrate.py --workload <cell> --seeds 12 [--first-seed N] [--dry-run]

Prints one JSON line per seed and a summary last: for each number the
largest sound reading, the smallest control reading, and their geometric
mean.  No measured window: training's readings need none.
"""

import time

STARTED = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_400_000_011)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on this many of the seeds (default: all)")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import manifest
    from benchmark.run import pin_platform

    cell = manifest.load_cell(args.workload, dry=args.dry_run)
    devices = pin_platform(args.dry_run, cell.chips)

    import jax

    from bagua_tpu.env import setup_compile_cache
    from benchmark import check, harness

    setup_compile_cache()
    if not args.dry_run:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = harness.CompileCounter()
    control_seeds = args.seeds if args.control_seeds is None else args.control_seeds
    built, sound, control = None, [], []
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        run = harness.Run(cell, seed, STARTED, devices, compiles=compiles)
        run.build(built)
        built = built or run
        t0 = time.perf_counter()
        run.setup()
        run.watcher.close()
        run.free_program(close=False)
        # a process's peak never falls: where the reference leaves it as the program's steps
        # left it, the check lies under the program
        peak_of_program = harness.device_section(devices)["memory_peak_bytes"]
        t1 = time.perf_counter()
        ref = run.reference()
        t2 = time.perf_counter()
        line = {"workload": cell.name, "seed": seed, "sound": run.numbers(ref),
                "worst_leaves": run.worst_leaves,
                "losses": run.warmup_losses, "reference_losses": ref[0],
                "setup_s": t1 - t0, "reference_s": t2 - t1,
                "memory_peak_bytes": {
                    "after_the_program": peak_of_program,
                    "after_the_reference": harness.device_section(devices)["memory_peak_bytes"]}}
        sound.append(line["sound"])
        if n < control_seeds:
            low = run.reference(control=True)
            line["control"], line["control_worst_leaves"] = check.compare(*low, *ref, head=cell.adapter.HEAD_LEAF)
            line["control_losses"] = low[0]
            control.append(line["control"])
        if args.dry_run:
            line["dry_run"] = True
        print(json.dumps(line), flush=True)
    built.trainer.close()
    summary = {}
    for name in sound[0]:
        high = max(s[name] for s in sound)
        summary[name] = {"sound_max": high, "sound_min": min(s[name] for s in sound)}
        lows = [c[name] for c in control if name in c]
        if lows:
            summary[name]["control_min"] = min(lows)
            summary[name]["control_max"] = max(lows)
            summary[name]["geometric_mean"] = (high * min(lows)) ** 0.5 if high > 0 else None
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "control_seeds": len(control), "summary": summary,
                      "device": harness.device_section(devices),
                      **({"dry_run": True} if args.dry_run else {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
