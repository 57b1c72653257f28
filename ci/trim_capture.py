#!/usr/bin/env python3
"""Cuts a profiler capture from the chip down to a fixture small enough to
commit, keeping what ``trace_analysis.summarize_capture`` reads.

One device, one run of the step's module: its ``XLA Modules``, ``XLA Ops``
and ``Async XLA Ops`` events, each named ``%name = <what it produces>
opcode(<operand shapes>)`` (layouts and operand names dropped; operand
shapes kept for collectives alone, whose bytes are read from them), each
with the frames of its ``op_name`` that the reduction parses as an
``op_name`` statistic (the join through the compiled step's text is made
here, once: the text has megabytes).  From the host: the ``bagua_fit``
iteration that dispatched the run with the ``bagua_host/…`` spans inside it,
and the benchmark's ``data`` spans during the run.

Read with ``jax.profiler.ProfileData`` and written through its text-proto
converter: JAX alone.

    python ci/trim_capture.py <capture dir> <out.xplane.pb> --hlo step.hlo.txt --run 1
"""

import argparse
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable from any cwd without an editable install
    sys.path.insert(0, REPO)

_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r" %[^,()]+")


def xspace_bytes(planes) -> bytes:
    """A serialized ``XSpace`` from ``[(plane name, [(line name, [(event
    name, start_ns, duration_ns, {stat: int or str})])])]``.  Strings are
    written once each, as references."""
    from jax.profiler import ProfileData

    def quoted(s):
        return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'

    out = []
    for plane_id, (plane_name, lines) in enumerate(planes, 1):
        names, stat_names = {}, {}
        out.append(f"planes {{ id: {plane_id} name: {quoted(plane_name)}")
        for line_id, (line_name, events) in enumerate(lines, 1):
            out.append(f"lines {{ id: {line_id} name: {quoted(line_name)} timestamp_ns: 0")
            for name, start_ns, duration_ns, stats in events:
                meta = names.setdefault(name, len(names) + 1)
                out.append(f"events {{ metadata_id: {meta} offset_ps: {int(start_ns) * 1000} "
                           f"duration_ps: {int(duration_ns) * 1000}")
                for key, value in stats.items():
                    key_id = stat_names.setdefault(key, len(stat_names) + 1)
                    if isinstance(value, str):
                        ref = stat_names.setdefault(value, len(stat_names) + 1)
                        out.append(f"stats {{ metadata_id: {key_id} ref_value: {ref} }}")
                    else:
                        out.append(f"stats {{ metadata_id: {key_id} int64_value: {int(value)} }}")
                out.append("}")
            out.append("}")
        for name, meta in names.items():
            out.append(f"event_metadata {{ key: {meta} value {{ id: {meta} name: {quoted(name)} }} }}")
        for name, meta in stat_names.items():
            out.append(f"stat_metadata {{ key: {meta} value {{ id: {meta} name: {quoted(name)} }} }}")
        out.append("}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def short_text(text: str, keep_operands: bool) -> str:
    """``%psum.7 = f32[8] all-reduce(f32[8])`` from the instruction's text."""
    from bagua_tpu.observability.trace_analysis import _OPCODE

    opcode = _OPCODE.search(text)
    if " = " not in text or not opcode:
        return text
    head = _LAYOUT.sub("", text[:opcode.end()])
    operands = ""
    if keep_operands:
        rest = _OPERAND.sub("", _LAYOUT.sub("", text[opcode.end():]))
        operands = rest[:rest.index(")")] if ")" in rest else rest
    return f"{head}{operands})"


def short_op_name(op_name: str) -> str:
    """The frames of an ``op_name`` that the reduction parses, in order."""
    from bagua_tpu.observability.scope_grammar import (
        EXCHANGE_RE, MP_RE, OVERLAP_BWD_RE, STEP_RE)

    frames = [m.group(0) for m in (STEP_RE.search(op_name),) if m]
    if "transpose(" in op_name:
        frames.append("transpose(")
    frames += [m.group(0) for pattern in (OVERLAP_BWD_RE, EXCHANGE_RE, MP_RE)
               for m in (pattern.search(op_name),) if m]
    return "/".join(frames)


def trim(capture: str, hlo_text, device: int, run: int):
    from jax.profiler import ProfileData

    from bagua_tpu.observability import trace_analysis as ta
    from bagua_tpu.observability.scope_grammar import FIT_STEP, hlo_op_labels

    labels = hlo_op_labels(hlo_text)[1] if hlo_text else {}
    data = ProfileData.from_file(ta.find_capture(capture))
    plane = next(p for p in data.planes if p.name == f"/device:TPU:{device}")
    lines = {line.name: list(line.events) for line in plane.lines}
    longest = max(lines[ta._MODULES], key=lambda e: e.duration_ns).name
    runs = sorted((e for e in lines[ta._MODULES] if e.name == longest), key=lambda e: e.start_ns)
    start, end = runs[run].start_ns, runs[run].start_ns + runs[run].duration_ns

    def within(e):
        return start <= e.start_ns and e.start_ns + e.duration_ns <= end

    device_lines = []
    for line_name in (ta._MODULES, ta._OPS, ta._ASYNC_OPS):
        events = []
        for e in filter(within, lines.get(line_name, ())):
            name = e.name.split(" = ", 1)[0].lstrip("%")
            opcode = ta._OPCODE.search(e.name)
            collective = bool(opcode and opcode.group(1).startswith(ta.COLLECTIVE_OPS))
            label = short_op_name(labels.get(name, ""))
            events.append((short_text(e.name, collective), e.start_ns, e.duration_ns,
                           {"op_name": label} if label else {}))
        device_lines.append((line_name, events))

    # the host: the iteration whose dispatch was this run's, and the
    # benchmark's own spans during the run
    host = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name in (FIT_STEP, "data") or ta.parse_host_span(e.name) is not None]
    fits = sorted((h for h in host if h[0] == FIT_STEP
                   and any(d[0] == "bagua_host/step/dispatch"
                           and h[1] <= d[1] and d[1] + d[2] <= h[1] + h[2] for d in host)),
                  key=lambda h: h[1])
    kept = []
    if run < len(fits):
        _, fit_start, fit_duration, _ = fits[run]
        kept += [(n, s, d, {k: v for k, v in stats.items() if k == "step_num"})
                 for n, s, d, stats in host
                 if n != "data" and fit_start <= s and s + d <= fit_start + fit_duration]
    kept += [(n, s, d, {}) for n, s, d, _ in host if n == "data" and s < end and s + d > start]
    return [(plane.name, device_lines), ("/host:CPU", [("python3", sorted(kept, key=lambda h: h[1]))])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture", help="profiler log dir or .xplane.pb")
    ap.add_argument("target")
    ap.add_argument("--hlo", help="the compiled step's text (the join to the labels)")
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--run", type=int, default=1, help="which run of the step's module")
    args = ap.parse_args(argv)
    hlo_text = None
    if args.hlo:
        with open(args.hlo) as f:
            hlo_text = f.read()
    blob = xspace_bytes(trim(args.capture, hlo_text, args.device, args.run))
    with open(args.target, "wb") as f:
        f.write(blob)
    print(f"{args.target}: {len(blob)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
