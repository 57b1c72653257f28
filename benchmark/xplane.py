"""The reduction from a profiler capture (``.xplane.pb``) to numbers: device
busy time, collective time and the part of it no other operation covers, the
operations that took most time, and the longest idle gaps named by what the
host was doing.  Read with ``jax.profiler.ProfileData`` alone.

A device plane is ``/device:TPU:<n>``.  Its ``XLA Ops`` line holds one event
per executed HLO operation, named by the instruction's text; ``Async XLA
Ops`` holds the spans of asynchronous operations from start to done (copies,
and collectives where the compiler made them asynchronous); ``XLA Modules``
one event per run of a compiled program.  A collective is known by its
opcode, not by its name.  Host spans are the benchmark's own
``TraceAnnotation("data")`` inside its batch iterator; the capture lies
wholly inside ``Trainer.fit``, so a gap under no ``data`` span is the fit
loop's.
"""

import re

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS, ASYNC_OPS, MODULES = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_SPAN = "data"
COLLECTIVE = re.compile(
    r" (all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)(-start|-done)?$")
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def op_name(text: str) -> str:
    """``fusion.46 f32[1,1024,30522] fusion`` from ``%fusion.46 = f32[1,1024,30522]{1,2,0:T(8,128)}
    fusion(...)``: the instruction's name, what it produces (which tells one
    fusion from another) and its opcode (which tells a collective whatever
    it is called: JAX names an all-reduce ``psum.7``)."""
    name, equals, rest = text.partition(" = ")
    if not equals:
        return text
    produces = re.split(r"[{ ]", rest.lstrip("("), maxsplit=1)[0]
    opcode = OPCODE.search(rest)
    return " ".join(filter(None, [name.lstrip("%"), produces, opcode and opcode.group(1)]))


def load(path: str) -> dict:
    """``{"devices": {n: {line name: [(name, start_ns, end_ns), ...]}},
    "host": [(start_ns, end_ns), ...]}`` from an ``.xplane.pb``."""
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS, ASYNC_OPS, MODULES):
                devices.setdefault(int(m.group(1)), {})[line.name] = sorted(
                    ((op_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
                     for e in line.events), key=lambda e: e[1:])
            elif plane.name.startswith("/host:"):
                host.extend((int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events if e.name == HOST_SPAN)
    return {"devices": devices, "host": sorted(host)}


def union(intervals):
    """Disjoint, sorted ``[start, end]`` lists covering ``intervals``."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def covered(intervals) -> int:
    return sum(end - start for start, end in union(intervals))


def bare(intervals, cover) -> int:
    """Length of ``intervals``' union that ``cover``'s union leaves bare."""
    intervals, cover = list(intervals), list(cover)
    return covered(intervals + cover) - covered(cover)


def _host_name(host, start, end) -> str:
    return "data" if any(s < end and e > start for s, e in host) else "fit-loop"


def reduce_device(lines: dict, host) -> dict:
    """One device's numbers over the span from its first operation's start
    to its last one's end."""
    ops = lines[OPS]
    start = min(s for _, s, _ in ops)
    end = max(e for _, _, e in ops)
    busy = union((s, e) for _, s, e in ops)
    collectives = [(s, e) for n, s, e in ops + lines.get(ASYNC_OPS, []) if COLLECTIVE.search(n)]
    others = [(s, e) for n, s, e in ops if not COLLECTIVE.search(n)]
    by_name = {}
    for n, s, e in ops:
        by_name[n] = by_name.get(n, 0) + (e - s)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])), reverse=True)
    # a step is a run of the program that took most of the time
    by_module = {}
    for n, s, e in lines.get(MODULES, []):
        runs = by_module.setdefault(n, [0, 0])
        runs[0] += e - s
        runs[1] += 1
    return {
        "window_ns": end - start,
        "busy_ns": sum(e - s for s, e in busy),
        "steps": max(by_module.values())[1] if by_module else None,
        "collective_ns": covered(collectives),
        "exposed_collective_ns": bare(collectives, others),
        "top_ops": sorted(by_name.items(), key=lambda kv: -kv[1])[:10],
        "top_gaps": [(_host_name(host, s, e), length) for length, s, e in gaps[:5]],
    }


def reduce(loaded: dict):
    """The capture's numbers, or None where no device operation was
    captured.  ``busy_s`` is averaged over the devices; steps, collectives,
    operations and gaps are device 0's."""
    devices = {n: lines for n, lines in loaded["devices"].items() if lines.get(OPS)}
    if not devices:
        return None
    per_device = {n: reduce_device(lines, loaded["host"]) for n, lines in devices.items()}
    first = per_device[min(per_device)]
    return {
        "devices": len(per_device),
        "steps": first["steps"],
        "window_s": max(d["window_ns"] for d in per_device.values()) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device.values()) / len(per_device) / 1e9,
        "device0_window_s": first["window_ns"] / 1e9,
        "device0_busy_s": first["busy_ns"] / 1e9,
        "collective_s": first["collective_ns"] / 1e9,
        "exposed_collective_s": first["exposed_collective_ns"] / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in first["top_ops"]],
        "idle_gaps": [[n, t / 1e9] for n, t in first["top_gaps"]],
    }
