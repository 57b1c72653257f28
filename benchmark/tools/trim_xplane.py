#!/usr/bin/env python3
"""Cuts a profiler capture down to a file small enough to commit: the device
planes named, their ``XLA Ops``, ``Async XLA Ops`` and ``XLA Modules`` lines
within the first ``--steps`` runs of the step program, the host's ``data``
spans in that stretch, event names cut to what ``xplane.op_name`` keeps, and
no statistics.  Needs tensorflow's ``xplane_pb2`` to write the file (the
benchmark reads captures with JAX alone); run by hand, once per recorded
trace:

    python benchmark/tools/trim_xplane.py <in.xplane.pb> <out.xplane.pb> --devices 0 1 --steps 2
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("source")
    ap.add_argument("target")
    ap.add_argument("--devices", type=int, nargs="+", default=[0])
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark import xplane

    space = xplane_pb2.XSpace()
    with open(args.source, "rb") as f:
        space.ParseFromString(f.read())
    keep_planes = {f"/device:TPU:{n}" for n in args.devices}
    keep_lines = {xplane.OPS, xplane.ASYNC_OPS, xplane.MODULES}

    def absolute(line, event):
        return line.timestamp_ns * 1000 + event.offset_ps

    # the stretch: from the first run of the step program to the end of run --steps
    first = next(p for p in space.planes if p.name == f"/device:TPU:{args.devices[0]}")
    modules = next(l for l in first.lines if l.name == xplane.MODULES)
    longest = max(modules.events, key=lambda e: e.duration_ps).metadata_id
    runs = sorted((absolute(modules, e), absolute(modules, e) + e.duration_ps)
                  for e in modules.events if e.metadata_id == longest)
    start, end = runs[0][0], runs[args.steps - 1][1]

    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name in keep_planes
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        names = {}
        for line in plane.lines:
            if device and line.name not in keep_lines:
                continue
            kept = [e for e in line.events
                    if start <= absolute(line, e) and absolute(line, e) + e.duration_ps <= end
                    and (device or plane.event_metadata[e.metadata_id].name == xplane.HOST_SPAN)]
            if not kept:
                continue
            new_line = new.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for e in kept:
                # the short name as a line of HLO that ``op_name`` reads back to itself
                short = xplane.op_name(plane.event_metadata[e.metadata_id].name).split(" ")
                name = (f"%{short[0]} = {short[1]} {short[2]}()" if len(short) == 3
                        else " ".join(short))
                meta_id = names.setdefault(name, len(names) + 1)
                new_line.events.add(metadata_id=meta_id, offset_ps=e.offset_ps,
                                    duration_ps=e.duration_ps)
        for name, meta_id in names.items():
            new.event_metadata[meta_id].id = meta_id
            new.event_metadata[meta_id].name = name
    with open(args.target, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{args.target}: {os.path.getsize(args.target)} bytes, "
          f"{sum(len(l.events) for p in out.planes for l in p.lines)} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
