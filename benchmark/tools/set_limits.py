#!/usr/bin/env python3
"""Writes the limits of ``correct`` into a cell's own file from the readings
of ``calibrate.py`` (its output, one JSON line per seed and a summary last),
by the rule PERF.md states: the geometric mean of the sound runs' largest
reading and the control's smallest, and never under three times the sound
runs' largest.  So a number that the control moves ninefold or more sits
midway (in ratio) between the two; one that it moves less, or hardly (the
loss, the norm of the parameters' change), is held against the fault it is
there to catch at three times the sound runs' largest; an exact comparison
has the limit 0.  The control has to fail one of a cell's numbers, not each.

    python benchmark/tools/set_limits.py <calibrate output> [--runs <run output> ...] [--toy]

``--runs`` folds in the numbers that whole runs of ``run.py`` printed (the
last line's ``checks``) as further sound readings: more seeds, and the same
programs the driver's check will run.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
EXACT = ("replica_mismatches",)


def limits(summary: dict, origin: str) -> dict:
    out = {"_readings": origin}
    for name, r in summary.items():
        if name in EXACT:
            out[name] = {"sound_max": r["sound_max"], "limit": 0}
            continue
        record = {"sound_max": r["sound_max"], "control_min": r.get("control_min")}
        middle = (r["sound_max"] * (r.get("control_min") or 0.0)) ** 0.5
        if middle >= 3 * r["sound_max"]:
            record["limit"] = float(f"{middle:.3g}")
            record["rule"] = "geometric mean of sound_max and control_min"
        else:
            record["limit"] = float(f"{3 * r['sound_max']:.3g}")
            record["rule"] = "3 x sound_max"
        record["control_fails_it"] = bool(
            r.get("control_min") is not None and r["control_min"] > record["limit"])
        out[name] = record
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("calibration")
    ap.add_argument("--runs", nargs="*", default=[], help="outputs of run.py to fold in")
    ap.add_argument("--toy", action="store_true", help="write toy_tolerances (a CPU rehearsal)")
    args = ap.parse_args(argv)

    def last_line(path):
        with open(path) as f:
            return json.loads([line for line in f if line.strip()][-1])

    last = last_line(args.calibration)
    seeds = set()
    for path in args.runs:
        run = last_line(path)
        if run["workload"] != last["workload"] or run.get("dry_run"):
            sys.exit(f"{path} is not a chip run of {last['workload']}")
        seeds.add(run["seed"])
        for name, value in run["checks"].items():
            record = last["summary"][name]
            record["sound_max"] = max(record["sound_max"], value)
    if bool(last.get("dry_run")) != args.toy:
        sys.exit("a dry run's readings are toy limits, and only they are")
    origin = (f"calibrate.py, {last['seeds']} seeds sound and {last['control_seeds']} control"
              + (f", and whole runs of run.py on {len(seeds)} more seeds" if seeds else "")
              + f"; {last['device']['kind']} x{last['device']['count']} (PR 25)")
    path = os.path.join(ROOT, "benchmark", "workloads", last["workload"] + ".json")
    with open(path) as f:
        cell = json.load(f)
    cell["toy_tolerances" if args.toy else "tolerances"] = limits(last["summary"], origin)
    with open(path, "w") as f:
        json.dump(cell, f, indent=2)
        f.write("\n")
    print(json.dumps(cell["toy_tolerances" if args.toy else "tolerances"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
