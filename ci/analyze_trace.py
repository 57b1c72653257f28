#!/usr/bin/env python3
"""The step's anatomy and the measured overlap from a profiler capture.

CLI face of :mod:`bagua_tpu.observability.trace_analysis`: point it at a
profiler log dir (``Trainer(profile_dir=...)`` /
``bagua_tpu.observability.ProfilerSession`` output, an ``.xplane.pb``
somewhere under it) and it prints what ``trainer.profile_summary`` held at
the end of the capturing ``fit`` call (:func:`summarize_capture`: the
device's busy time by step phase, the exchange operation by operation, host
spans, idle gaps by host span, the lead of every step's dispatch), and with
``--overlap`` the per-bucket hidden fraction (:func:`analyze_trace`).

Attribution needs the compiled text of the captured step (the join is
instruction name → ``op_name`` metadata → scope label).  ``Trainer`` leaves
it beside its capture as ``step.hlo.txt`` and it is picked up from there;
pass another with ``--hlo``.  Without it every operation is ``unattributed``
and only collectives are told (by opcode).

Usage::

    python ci/analyze_trace.py /tmp/bagua_trace
    python ci/analyze_trace.py /tmp/bagua_trace --hlo step.hlo.txt --overlap
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable from any cwd without an editable install
    sys.path.insert(0, REPO)

from bagua_tpu.observability.trace_analysis import (
    STEP_TEXT_FILE,
    analyze_trace,
    format_partition,
    summarize_capture,
)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", help="profiler log dir or .xplane.pb path")
    ap.add_argument(
        "--hlo", default=None,
        help=f"compiled HLO text of the captured step (default: {STEP_TEXT_FILE} "
        "in the log dir, where Trainer leaves it)",
    )
    ap.add_argument("--device", type=int, default=0, help="which device's steps")
    ap.add_argument(
        "--overlap", action="store_true",
        help="print the per-bucket hidden fraction (analyze_trace) instead",
    )
    ap.add_argument(
        "--module", default=None,
        help="with --overlap: restrict to events of this hlo_module (default: "
        "the module named in the HLO text, or all modules)",
    )
    ap.add_argument("--out", default=None, help="also write the report as JSON")
    args = ap.parse_args()

    hlo = args.hlo
    if hlo is None and os.path.isfile(os.path.join(args.trace_dir, STEP_TEXT_FILE)):
        hlo = os.path.join(args.trace_dir, STEP_TEXT_FILE)
    hlo_text = None
    if hlo:
        with open(hlo) as f:
            hlo_text = f.read()
    if args.overlap:
        report = analyze_trace(args.trace_dir, hlo_text=hlo_text, module=args.module)
    else:
        report = summarize_capture(args.trace_dir, hlo_text=hlo_text, device=args.device)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    if report is None:
        print(f"\nno operation of device {args.device} in the capture", file=sys.stderr)
    elif args.overlap:
        print(
            f"\nmeasured_overlap_frac = {report['measured_overlap_frac']} over "
            f"{report['collective_spans']} collective spans "
            f"({report['collective_ms']} ms on the wire, "
            f"{report['hidden_ms']} ms hidden under compute)",
            file=sys.stderr,
        )
    else:
        print("\n" + format_partition(report), file=sys.stderr)


if __name__ == "__main__":
    main()
