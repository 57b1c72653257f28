"""The program's own reduction of a profiler capture: measure — don't
assert — where a step's time goes and how much of the exchange is hidden.

Input is the ``.xplane.pb`` that :class:`~bagua_tpu.observability.core.ProfilerSession`
/ ``jax.profiler.start_trace`` writes (``Trainer(profile_dir=...)`` captures
one), read with ``jax.profiler.ProfileData`` alone, on both backends:

- on the chip a device is a ``/device:TPU:<n>`` plane.  Its ``XLA Ops`` line
  holds one event per executed HLO operation, named by the instruction's
  text; ``Async XLA Ops`` the spans of asynchronous operations from start to
  done; ``XLA Modules`` one event per run of a compiled program;
- on the CPU every executed operation is an event with ``hlo_op``,
  ``hlo_module``, ``run_id`` and ``device_ordinal`` statistics on the
  executor's thread lines of the ``/host:CPU`` plane;
- host annotations (``bagua_fit`` with its ``step_num``, ``bagua_host/…``,
  see :mod:`~bagua_tpu.observability.annotations`) are events of the
  ``/host:CPU`` plane's thread lines, on the same clock.

Attribution: the capture's events carry the instruction's name but not its
``op_name`` metadata, where the ``jax.named_scope`` labels live (looked at on
a v5e capture, jax 0.9.0: the statistics of an ``XLA Ops`` event are its
device offset and duration).  The join runs through the compiled module's
text (``compiled.as_text()``): instruction name → ``op_name`` →
``bagua_step`` / ``bagua_ex`` / ``bagua_overlap_bwd`` frames, a model's own
``bagua_model/part=`` (``attn_core`` … ``exit_gate``) and ``bagua_model/pass=``
frames, and ``jax.checkpoint``'s ``rematted_computation``.  An event that
does carry an ``op_name`` statistic is read there.  Without the text every
operation is ``unattributed`` but collectives are still told, by opcode.

Two reductions share the loader and the interval arithmetic:

:func:`analyze_trace`
    per labeled ``(algo, bucket)`` and per model-parallel scope, the
    fraction of each collective's duration hidden under compute
    (``measured_overlap_frac = hidden / total``; 1.0 = fully hidden wire,
    0.0 = strictly serialized exchange; T3-style, arXiv:2401.16677);

:func:`summarize_capture`
    one device's captured steps: the partition of its busy time by step
    phase (``recompute``, what ``jax.checkpoint`` runs again inside the
    backward pass, is a class of its own), a model's time by part and, where
    its stack runs several times, by pass, the exchange operation by operation (where each collective sits
    relative to the backward pass), host spans, each idle gap put down to the
    host span open when it began, and for every step how long its request
    waited in the device's queue.
"""

import bisect
import glob
import heapq
import logging
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bagua_tpu.observability.scope_grammar import (
    EXCHANGE_RE,
    FIT_STEP,
    MODEL_PREFIX,
    MP_RE,
    OVERLAP_BWD_RE,
    PASS_RE,
    hlo_op_labels,
    parse_exchange_label,
    parse_host_span,
    parse_model_part,
    parse_model_pass,
    parse_mp_label,
    parse_step_phase,
)

logger = logging.getLogger(__name__)

__all__ = [
    "COLLECTIVE_OPS",
    "STEP_TEXT_FILE",
    "find_capture",
    "load_trace_events",
    "hlo_op_labels",
    "analyze_trace",
    "analyze_events",
    "phase_of",
    "summarize_capture",
    "last_summary",
    "format_partition",
]

#: HLO opcodes (and instruction-name prefixes) that move bytes between devices
COLLECTIVE_OPS = (
    "all-reduce",
    "reduce-scatter",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)

#: where ``Trainer`` leaves the compiled step's text beside its capture
STEP_TEXT_FILE = "step.hlo.txt"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS, _ASYNC_OPS, _MODULES = "XLA Ops", "Async XLA Ops", "XLA Modules"
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\]")
_BITS = re.compile(r"[0-9]+")

#: the step phases the partition renames: what runs under ``fwd_bwd`` is
#: split by autodiff's ``transpose(`` frame, the two update paths are one
_OPTIMIZER_PHASES = ("optimizer", "sharded_update")
#: the frame ``jax.checkpoint`` writes around the operations of the forward
#: pass that it runs again inside the backward pass
_RECOMPUTE_FRAME = "rematted_computation"
#: the classes of the partition that are the model's own work
_MODEL_CLASSES = ("forward", "backward", "recompute")
#: a layer's scope right under a pass's, where a part's scope is nested inside
#: it (``bagua_model/pass=2/layer_1/attn/bagua_model/part=attn_core``)
_LAYER_UNDER_PASS = re.compile(
    PASS_RE.pattern + r"/(?!" + MODEL_PREFIX + r"/)(?P<layer>[^/]+)/(?:[^\"]*/)?"
    + MODEL_PREFIX + "/part=")


# -- the capture --------------------------------------------------------------


def find_capture(log_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a profiler log dir (the capture lands in
    ``plugins/profile/<timestamp>/<host>.xplane.pb``), or ``log_dir`` itself
    when it is such a file."""
    if log_dir.endswith(".xplane.pb"):
        return log_dir if os.path.isfile(log_dir) else None
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _whole_fields(blob: bytes) -> bytes:
    """The leading top-level fields (planes) of a serialized ``XSpace`` that
    are whole: what a capture cut off mid-write still holds."""

    def varint(i):
        value = shift = 0
        while True:
            byte = blob[i]  # IndexError at the cut
            value |= (byte & 0x7F) << shift
            i, shift = i + 1, shift + 7
            if not byte & 0x80:
                return value, i

    whole = 0
    try:
        while whole < len(blob):
            tag, i = varint(whole)
            wire = tag & 7
            if wire == 0:
                _, i = varint(i)
            elif wire == 1:
                i += 8
            elif wire == 2:
                length, i = varint(i)
                i += length
            elif wire == 5:
                i += 4
            else:
                break  # no protobuf
            if i > len(blob):
                break
            whole = i
    except IndexError:
        pass
    return blob[:whole]


def _profile_data(path: str):
    from jax.profiler import ProfileData

    try:
        return ProfileData.from_file(path)
    except Exception as e:
        # a truncated capture (job killed mid-profile) is the common case,
        # not a parse bug: degrade to the planes written whole
        with open(path, "rb") as f:
            salvaged = _whole_fields(f.read())
        logger.warning(
            "capture %s truncated/corrupt (%s); analyzing the %d bytes of whole planes",
            path, e, len(salvaged),
        )
        try:
            return ProfileData.from_serialized_xspace(salvaged)
        except Exception:
            return ProfileData.from_serialized_xspace(b"")


def _stat(event, key: str):
    return next((value for name, value in event.stats if name == key), None)


def _read_capture(log_dir: str, device: Optional[int] = None) -> Dict:
    """``{"ops": [event], "modules": {device: [(name, start, end)]},
    "host": [(name, start, end, step_num)]}``, times in microseconds.  An
    ``ops`` event is what :func:`load_trace_events` documents.  ``device``:
    of a chip's planes read that device's alone."""
    path = find_capture(log_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    capture = {"ops": [], "modules": {}, "host": []}
    for plane in _profile_data(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            _read_host_plane(plane, capture)
        elif device is None or int(m.group(1)) == device:
            _read_device_plane(plane, int(m.group(1)), capture)
    for runs in capture["modules"].values():
        runs.sort(key=lambda run: run[1:])
    capture["host"].sort(key=lambda span: span[1:3])
    return capture


def _read_device_plane(plane, device: int, capture: Dict) -> None:
    """A chip's plane: its line of module runs, and the operations of its two
    lines, each put down to the run it lies in."""
    lines = {line.name: line.events for line in plane.lines}
    runs = sorted((e.start_ns / 1e3, (e.start_ns + e.duration_ns) / 1e3, e.name.split("(")[0])
                  for e in lines.get(_MODULES, ()))
    capture["modules"][device] = [(name, start, end) for start, end, name in runs]
    run_starts = [start for start, _, _ in runs]
    for line_name in (_OPS, _ASYNC_OPS):
        for e in lines.get(line_name, ()):
            text, ts = e.name, e.start_ns / 1e3
            k = bisect.bisect_right(run_starts, ts) - 1
            opcode = _OPCODE.search(text)
            capture["ops"].append({
                "hlo_op": text.split(" = ", 1)[0].lstrip("%"),
                "hlo_module": runs[k][2] if k >= 0 and ts < runs[k][1] else "",
                "lane": (device, line_name),
                "ts": ts,
                "dur": e.duration_ns / 1e3,
                "opcode": opcode.group(1) if opcode else None,
                "text": text,
                "op_name": _stat(e, "op_name"),
            })


def _read_host_plane(plane, capture: Dict) -> None:
    """Any other plane: the program's annotations, and on the CPU the
    executor threads' events that carry an operation as statistics."""
    cpu_runs = {}
    for line in plane.lines:
        for e in line.events:
            name, ts, dur = e.name, e.start_ns / 1e3, e.duration_ns / 1e3
            if name == FIT_STEP or parse_host_span(name) is not None:
                capture["host"].append((name, ts, ts + dur, _stat(e, "step_num")))
                continue
            if name.startswith("$"):
                continue  # the Python tracer's frames: many, and never an operation
            stats = dict(e.stats)
            if "hlo_op" not in stats:
                continue
            device, module = int(stats.get("device_ordinal", 0)), stats.get("hlo_module", "")
            capture["ops"].append({
                "hlo_op": stats["hlo_op"],
                "hlo_module": module,
                "lane": (device, line.name),
                "ts": ts,
                "dur": dur,
                "opcode": None,
                "text": "",
                "op_name": stats.get("op_name"),
            })
            # the CPU has no line of module runs: a run is the span of the
            # operations that share its run_id
            run = cpu_runs.setdefault((device, stats.get("run_id"), module), [ts, ts + dur])
            run[0], run[1] = min(run[0], ts), max(run[1], ts + dur)
    for (device, _, module), (start, end) in cpu_runs.items():
        capture["modules"].setdefault(device, []).append((module, start, end))


def load_trace_events(log_dir: str) -> List[Dict]:
    """Every executed XLA operation in the newest capture under ``log_dir``
    (or in the ``.xplane.pb`` it names): ``{"hlo_op", "hlo_module", "lane",
    "ts", "dur"}`` with ``ts``/``dur`` in microseconds and ``lane`` =
    ``(device, line)``, plus ``"opcode"`` and ``"text"`` where the event is
    named by the instruction's text (the chip) and ``"op_name"`` where the
    event carries one.  Raises ``FileNotFoundError`` without a capture; a
    truncated or corrupt one degrades to the planes that were written whole."""
    return _read_capture(log_dir)["ops"]


# -- interval arithmetic ------------------------------------------------------


def _merge_intervals(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals.sort()
    merged = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(iv) for iv in merged]


def _covered(start: float, end: float, merged: List[Tuple[float, float]],
             starts: List[float]) -> float:
    """Length of [start, end] ∩ union(merged) (merged sorted, disjoint)."""
    if end <= start or not merged:
        return 0.0
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, start) - 1)
    while i < len(merged) and merged[i][0] < end:
        s, e = merged[i]
        covered += max(0.0, min(e, end) - max(s, start))
        i += 1
    return covered


def _length(merged: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _owned(spans: List[Tuple[float, float]]) -> List[float]:
    """For spans that may nest or overlap (a ``while`` around its body, the
    CPU's executor threads), the time each one *owns*: every instant belongs
    to the span that started last among those running.  The owned times add
    up to the length of the union, so a partition built on them is one."""
    owned = [0.0] * len(spans)
    running = []  # (-start, end, index): the top started last
    now = 0.0

    def advance(to):
        nonlocal now
        while running and now < to:
            _, end, i = running[0]
            if end <= now:
                heapq.heappop(running)
                continue
            upto = min(end, to)
            owned[i] += upto - now
            now = upto
        now = max(now, to)

    for i in sorted(range(len(spans)), key=lambda i: spans[i]):
        advance(spans[i][0])
        heapq.heappush(running, (-spans[i][0], spans[i][1], i))
    advance(float("inf"))
    return owned


# -- what an operation is -----------------------------------------------------

_HLO_OPCODE = re.compile(r"^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) = .*? ([a-z][a-z\-]*)\(", re.MULTILINE)


def _join(events: List[Dict], hlo_text: Optional[str]) -> str:
    """Fills in, from the compiled module's text, the ``op_name`` and the
    ``opcode`` of every event that does not carry its own; returns the
    module's name.  The join is by instruction name."""
    if not hlo_text:
        return ""
    module, labels = hlo_op_labels(hlo_text)
    opcodes = dict(_HLO_OPCODE.findall(hlo_text))
    for e in events:
        if not e.get("op_name"):
            e["op_name"] = labels.get(e["hlo_op"])
        if not e.get("opcode"):
            e["opcode"] = opcodes.get(e["hlo_op"])
    return module



def _is_collective(event: Dict) -> bool:
    """By opcode where the event gives it (JAX names an all-reduce
    ``psum.7``), else by the instruction name's prefix."""
    told = event.get("opcode") or event["hlo_op"].lstrip("%")
    return told.startswith(COLLECTIVE_OPS)


def phase_of(op_name: Optional[str]) -> str:
    """The partition's class of a non-collective operation from its
    ``op_name`` metadata: ``forward`` / ``backward`` (``fwd_bwd`` without /
    with autodiff's ``transpose(`` frame), ``recompute`` (``fwd_bwd`` inside
    ``jax.checkpoint``'s ``rematted_computation`` frame: operations of the
    forward pass run again inside the backward pass), ``optimizer``
    (``optimizer`` and ``sharded_update``), any other ``bagua_step`` phase by
    its own name, ``unattributed`` without one."""
    phase = parse_step_phase(op_name)
    if phase is None:
        return "unattributed"
    if phase == "fwd_bwd":
        if _RECOMPUTE_FRAME in op_name:
            return "recompute"
        return "backward" if "transpose(" in op_name else "forward"
    return "optimizer" if phase in _OPTIMIZER_PHASES else phase


def _exchange_label(op_name: Optional[str]) -> Optional[str]:
    """The ``bagua_ex`` (bucket or model-parallel) or ``bagua_overlap_bwd``
    frame an operation carries, as written."""
    for pattern in (EXCHANGE_RE, MP_RE, OVERLAP_BWD_RE):
        m = pattern.search(op_name or "")
        if m:
            return m.group(0)
    return None


def _operand_bytes(text: str) -> Optional[int]:
    """Bytes of a collective's operands from the shapes in its text: the
    operand list's where it names shapes, else what it produces (an
    all-reduce produces what it takes)."""
    opcode = _OPCODE.search(text)
    if not opcode:
        return None
    depth, braces, end = 1, 0, len(text)
    for i in range(opcode.end(), len(text)):
        c = text[i]
        if c == "{":
            braces += 1
        elif c == "}":
            braces -= 1
        elif braces == 0 and c in "()":
            depth += 1 if c == "(" else -1
            if depth == 0:
                end = i
                break
    shapes = _SHAPE.findall(text[opcode.end():end]) or _SHAPE.findall(text[:opcode.start()])
    if not shapes:
        return None
    total = 0
    for dtype, dims in shapes:
        # the first number in a type's name is an element's bits (bf16,
        # f8e4m3fn, s4, c64); a pred takes a byte
        bits = 8 if dtype == "pred" else int(_BITS.search(dtype).group(0))
        elements = 1
        for d in filter(None, dims.split(",")):
            elements *= int(d)
        total += (elements * bits + 7) // 8
    return total


# -- hidden fraction per bucket -----------------------------------------------


def analyze_trace(
    log_dir: str,
    hlo_text: Optional[str] = None,
    module: Optional[str] = None,
) -> Dict:
    """Per-bucket measured overlap efficiency from one profiler capture.

    Args:
        log_dir: profiler log dir (or a direct ``.xplane.pb`` path).
        hlo_text: compiled HLO of the step whose execution was captured;
            enables bucket attribution (instruction → ``op_name`` labels).
        module: restrict to events of this ``hlo_module`` (defaults to the
            module named in ``hlo_text``; None + no hlo_text = all modules).

    Returns a dict with the aggregate ``measured_overlap_frac``, a
    ``per_bucket`` list (one row per labeled ``(algo, bucket)``), a
    ``per_scope`` list (one row per model-parallel scope axis — ``tp``/``ep``
    exchanges labeled via :func:`~bagua_tpu.observability.annotations.mp_scope`,
    each row carrying its own ``measured_overlap_frac``), and an
    ``unattributed`` bucket for collective spans without any label.
    """
    return analyze_events(load_trace_events(log_dir), hlo_text=hlo_text, module=module)


def analyze_events(
    events: List[Dict],
    hlo_text: Optional[str] = None,
    module: Optional[str] = None,
) -> Dict:
    """:func:`analyze_trace` on events already loaded (or made by hand)."""
    module = module or _join(events, hlo_text)
    if module:
        scoped = [e for e in events if e["hlo_module"] == module]
        # a lowered-vs-executed name drift must degrade to "unattributed",
        # not to an empty analysis
        if scoped:
            events = scoped
    collectives = [e for e in events if _is_collective(e)]
    compute = [e for e in events if not _is_collective(e)]

    merged = _merge_intervals([(e["ts"], e["ts"] + e["dur"]) for e in compute])
    starts = [s for s, _ in merged]

    per_key: Dict[Tuple, Dict] = {}
    per_scope_key: Dict[str, Dict] = {}
    total_us = hidden_us = 0.0
    for e in collectives:
        hid = _covered(e["ts"], e["ts"] + e["dur"], merged, starts)
        total_us += e["dur"]
        hidden_us += hid
        op_name = e.get("op_name") or ""
        lab = parse_exchange_label(op_name)
        mp = None if lab else parse_mp_label(op_name)
        if mp is not None:
            srow = per_scope_key.setdefault(
                mp["axis"],
                {
                    "axis": mp["axis"],
                    "phases": set(),
                    "hlo_ops": set(),
                    "spans": 0,
                    "collective_us": 0.0,
                    "hidden_us": 0.0,
                },
            )
            srow["phases"].add(mp["phase"])
            srow["hlo_ops"].add(e["hlo_op"])
            srow["spans"] += 1
            srow["collective_us"] += e["dur"]
            srow["hidden_us"] += hid
            continue
        key = (lab["algo"], lab["bucket"]) if lab else None
        row = per_key.setdefault(
            key,
            {
                "algo": lab["algo"] if lab else None,
                "bucket": lab["bucket"] if lab else None,
                "phases": set(),
                "hlo_ops": set(),
                "spans": 0,
                "collective_us": 0.0,
                "hidden_us": 0.0,
            },
        )
        if lab:
            row["phases"].add(lab["phase"])
        row["hlo_ops"].add(e["hlo_op"])
        row["spans"] += 1
        row["collective_us"] += e["dur"]
        row["hidden_us"] += hid

    def finish(row):
        return {
            "algo": row["algo"],
            "bucket": row["bucket"],
            "phases": sorted(row["phases"]),
            "hlo_ops": sorted(row["hlo_ops"]),
            "spans": row["spans"],
            "collective_ms": round(row["collective_us"] / 1e3, 3),
            "hidden_ms": round(row["hidden_us"] / 1e3, 3),
            "overlap_frac": round(row["hidden_us"] / row["collective_us"], 4)
            if row["collective_us"] else 0.0,
        }

    def finish_scope(row):
        return {
            "axis": row["axis"],
            "phases": sorted(row["phases"]),
            "hlo_ops": sorted(row["hlo_ops"]),
            "spans": row["spans"],
            "collective_ms": round(row["collective_us"] / 1e3, 3),
            "hidden_ms": round(row["hidden_us"] / 1e3, 3),
            "measured_overlap_frac": round(
                row["hidden_us"] / row["collective_us"], 4
            )
            if row["collective_us"] else 0.0,
        }

    per_bucket = sorted(
        (finish(r) for k, r in per_key.items() if k is not None),
        key=lambda r: (r["algo"], r["bucket"]),
    )
    per_scope = sorted(
        (finish_scope(r) for r in per_scope_key.values()),
        key=lambda r: r["axis"],
    )
    unattributed = next(
        (finish(r) for k, r in per_key.items() if k is None), None
    )
    return {
        "module": module or "",
        "num_xla_events": len(events),
        "collective_spans": len(collectives),
        "collective_ms": round(total_us / 1e3, 3),
        "hidden_ms": round(hidden_us / 1e3, 3),
        "measured_overlap_frac": round(hidden_us / total_us, 4) if total_us else 0.0,
        "per_bucket": per_bucket,
        "per_scope": per_scope,
        "unattributed": unattributed,
    }


# -- the step's anatomy -------------------------------------------------------

_LAST_SUMMARY: Optional[Dict] = None


def last_summary() -> Optional[Dict]:
    """The most recent :func:`summarize_capture` result of this process.
    Process-wide for one reason: a harness that deletes the capture and
    closes the trainer before it reads per-layer numbers (``benchmark/run.py``
    does) has no handle on the program left, only this module."""
    return _LAST_SUMMARY


def summarize_capture(log_dir: str, hlo_text: Optional[str] = None, device: int = 0) -> Optional[Dict]:
    """One device's captured steps, from the newest capture under ``log_dir``.

    A *step* is a run of the module with most device time.  Per step,
    averaged over the captured runs (milliseconds):

    ``partition_ms``
        the device's busy time inside the step module, every operation of
        the operations' line in exactly one class: ``exchange`` (a
        collective, told by opcode), else :func:`phase_of` its label
        (``forward``, ``backward``, ``recompute``, ``optimizer``, ``restack``,
        ``algo_start`` … ``unattributed``; a class nothing ran in is left
        out, so a model that rebuilds nothing in its backward pass has no
        ``recompute``).  Sums to ``step_busy_ms``; with
        ``other_modules_ms`` (by module: a feed's batch maker) to
        ``busy_ms``; with ``idle_ms`` to ``window_ms``.  ``unattributed_top``
        names the ten operations with most unattributed time (those the
        compiler made and gave no metadata: layout copies, prefetches).
    ``model_part_ms``
        only for a model that names its parts (``bagua_model/part=...``):
        the ``forward``, ``backward`` and ``recompute`` time by part, all
        together (autodiff carries the frame into the backward pass, and
        ``jax.checkpoint`` into what it runs again there), and ``other`` for
        what runs under no part's name (norms, residual adds).  Sums to
        ``forward`` + ``backward`` + ``recompute`` of ``partition_ms``.
    ``model_pass_ms``, ``layer_applications_per_step``
        only for a model whose stack runs several times and names its passes
        (``bagua_model/pass=<t>``, ``models/ouro.py``): the same three
        classes' time by pass, keyed ``"1"``, ``"2"`` …, each operation under
        its own label (the passes are written out one after the other, so
        nothing is split by order); what runs under no pass (the lookup, the
        loss that joins the exits) is the rest of the three classes' sum.
        And how often a layer ran in one forward pass: the distinct ``(pass,
        layer)`` pairs among the step's ``forward`` operations, a layer being
        the scope right under a pass's with a part's scope nested inside it
        (a flax module's name).
    ``exchange``
        ``calls``, ``bytes``, ``collective_ms`` (union of the collectives'
        spans on both lines), ``exposed_ms`` (the part no other operation
        covers), ``tail_ms`` (the part after the backward's last operation
        has ended), and ``ops``: the first captured step's collectives one by
        one (``name``, ``bytes``, ``label``, ``start_after_first_backward_ms``,
        ``start_after_last_backward_ms``, ``ms``, ``covered_ms``,
        ``after_backward``).  Where the compiler has combined several labeled
        all-reduces into one, the row carries one constituent's label and
        the bytes of all: there is no per-bucket number left to give.
    ``host_spans_ms``, ``idle_by_host_span_ms``
        each ``bagua_host/…`` span's time, and the device's idle gaps by the
        innermost span open on the host when the gap began (``none``: no
        span was open).
    ``per_step``
        a row per ``bagua_fit`` iteration: ``step_num``, when its
        ``step/dispatch`` span ended, when the device began and ended the
        run (the n-th run in the capture is the n-th dispatch in it), and
        ``lead_ms`` between the two: how long the request sat in the
        device's queue.  Times from the device's first operation.

    ``hlo_text`` is the compiled step's text, the join to the labels.
    Returns None where the capture holds no operation of ``device``; the
    result is also kept for :func:`last_summary`.
    """
    global _LAST_SUMMARY
    capture = _read_capture(log_dir, device)
    runs = capture["modules"].get(device, [])
    on_device = [e for e in capture["ops"] if e["lane"][0] == device]
    ops = [e for e in on_device if e["lane"][1] != _ASYNC_OPS]
    if not ops or not runs:
        return None
    _join(on_device, hlo_text)

    by_module: Dict[str, float] = {}
    for name, start, end in runs:
        by_module[name] = by_module.get(name, 0.0) + end - start
    module = max(by_module, key=by_module.get)
    step_runs = [(s, e) for name, s, e in runs if name == module]
    steps = len(step_runs)

    spans = [(e["ts"], e["ts"] + e["dur"]) for e in ops]
    busy = _merge_intervals(list(spans))
    window_start, window_us = busy[0][0], busy[-1][1] - busy[0][0]
    busy_us = _length(busy)

    # the partition: each operation's owned time into its class
    partition: Dict[str, float] = {}
    other_modules: Dict[str, float] = {}
    unattributed: Dict[str, float] = {}
    model_parts: Dict[str, float] = {}
    model_passes: Dict[str, float] = {}
    layers_run = set()
    for e, owned in zip(ops, _owned(spans)):
        e["class"] = "exchange" if _is_collective(e) else phase_of(e.get("op_name"))
        if e["hlo_module"] != module:
            name = e["hlo_module"] or "none"
            other_modules[name] = other_modules.get(name, 0.0) + owned
            continue
        partition[e["class"]] = partition.get(e["class"], 0.0) + owned
        if e["class"] == "unattributed":
            unattributed[e["hlo_op"]] = unattributed.get(e["hlo_op"], 0.0) + owned
        elif e["class"] in _MODEL_CLASSES:
            part = parse_model_part(e.get("op_name")) or "other"
            model_parts[part] = model_parts.get(part, 0.0) + owned
            run = parse_model_pass(e.get("op_name"))
            if run is not None:
                model_passes[str(run)] = model_passes.get(str(run), 0.0) + owned
                layer = _LAYER_UNDER_PASS.search(e["op_name"]) if e["class"] == "forward" else None
                if layer:
                    layers_run.add((run, layer.group("layer")))

    def per_step_ms(us: float) -> float:
        return us / 1e3 / steps

    def per_step_dict(us_by_name: Dict[str, float]) -> Dict[str, float]:
        return {name: per_step_ms(us) for name, us in sorted(us_by_name.items())}

    labeled = any(e.get("op_name") for e in ops)
    exchange = _exchange_of(on_device, ops, spans, module, step_runs)
    for key in ("collective_ms", "exposed_ms", "tail_ms"):
        exchange[key] = None if exchange[key] is None else per_step_ms(exchange[key])
    exchange["calls"] /= steps
    host_us, idle_us, per_step = _host_side(capture["host"], busy, step_runs, window_start)
    summary = {
        "module": module,
        "device": device,
        "steps": steps,
        "labeled": labeled,
        "step_ms": per_step_ms(by_module[module]),
        "window_ms": per_step_ms(window_us),
        "busy_ms": per_step_ms(busy_us),
        "idle_ms": per_step_ms(window_us - busy_us),
        "idle_share": 1.0 - busy_us / window_us,
        "step_busy_ms": per_step_ms(sum(partition.values())),
        "partition_ms": per_step_dict(partition),
        "other_modules_ms": per_step_dict(other_modules),
        "unattributed_top": [
            {"name": name, "ms": per_step_ms(us)}
            for name, us in sorted(unattributed.items(), key=lambda kv: -kv[1])[:10]
        ] if labeled else [],
        "exchange": exchange,
        "host_spans_ms": per_step_dict(host_us),
        "idle_by_host_span_ms": per_step_dict(idle_us),
        "per_step": per_step,
    }
    if set(model_parts) - {"other"}:
        summary["model_part_ms"] = per_step_dict(model_parts)
    if model_passes:
        summary["model_pass_ms"] = per_step_dict(model_passes)
        summary["layer_applications_per_step"] = len(layers_run)
    _LAST_SUMMARY = summary
    return summary


def _exchange_of(on_device, ops, spans, module, step_runs) -> Dict:
    """The collectives of one device against everything else on its
    operations' line: totals over the capture in microseconds (``calls`` a
    count), and the first step's calls one by one.  ``ops`` carry their
    ``class``."""
    collectives = [e for e in on_device if _is_collective(e)]
    others = _merge_intervals([s for s, e in zip(spans, ops) if e["class"] != "exchange"])
    other_starts = [s for s, _ in others]
    wire = _merge_intervals([(e["ts"], e["ts"] + e["dur"]) for e in collectives])
    wire_starts = [s for s, _ in wire]
    exposed = _length(wire) - sum(_covered(s, e, others, other_starts) for s, e in wire)
    run_starts = [s for s, _ in step_runs]
    backward = {}  # run -> (first start, last end) of its backward operations
    for e in ops:
        if e["class"] == "backward" and e["hlo_module"] == module:
            k = bisect.bisect_right(run_starts, e["ts"]) - 1
            first, last = backward.get(k, (e["ts"], e["ts"]))
            backward[k] = (min(first, e["ts"]), max(last, e["ts"] + e["dur"]))
    tail = sum(_covered(max(step_runs[k][0], last), step_runs[k][1], wire, wire_starts)
               for k, (_, last) in backward.items()) if backward else None
    # one row per call: an asynchronous collective is its start on the
    # operations' line and lasts as long as its span on the other line
    calls = [e for e in ops
             if e["class"] == "exchange" and not (e["opcode"] or "").endswith("-done")]
    in_flight = {}
    for e in collectives:
        if e["lane"][1] == _ASYNC_OPS:
            in_flight.setdefault(e["hlo_op"], []).append(e)
    rows = []
    for e in calls:
        if not step_runs[0][0] <= e["ts"] < step_runs[0][1]:
            continue
        span = next((a for a in in_flight.get(e["hlo_op"], ()) if a["ts"] >= e["ts"] - 1.0), e)
        start, end = e["ts"], max(e["ts"] + e["dur"], span["ts"] + span["dur"])
        row = {
            "name": e["hlo_op"],
            "bytes": _operand_bytes(e["text"]),
            "label": _exchange_label(e.get("op_name")),
            "ms": (end - start) / 1e3,
            "covered_ms": _covered(start, end, others, other_starts) / 1e3,
        }
        if 0 in backward:
            row["start_after_first_backward_ms"] = (start - backward[0][0]) / 1e3
            row["start_after_last_backward_ms"] = (start - backward[0][1]) / 1e3
            row["after_backward"] = start >= backward[0][1]
        rows.append(row)
    known_bytes = [r["bytes"] for r in rows if r["bytes"] is not None]
    return {
        "calls": len(calls),
        "bytes": sum(known_bytes) if known_bytes else None,
        "collective_ms": _length(wire),
        "exposed_ms": exposed,
        "tail_ms": tail,
        "ops": rows,
    }


def _host_side(host, busy, step_runs, window_start):
    """``(host spans' time by name, idle gaps by the innermost span open when
    each began, a row per step)``; microseconds but for the rows."""
    fit_steps = [h for h in host if h[0] == FIT_STEP]
    host_spans = [(h[1], h[2], parse_host_span(h[0])) for h in host if h[0] != FIT_STEP]
    host_us: Dict[str, float] = {}
    for start, end, name in host_spans:
        host_us[name] = host_us.get(name, 0.0) + end - start
    # gaps and spans both come sorted by start: one sweep, the spans open at
    # a gap's start kept in the order they opened
    idle_us: Dict[str, float] = {}
    opened, following = [], 0
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        while following < len(host_spans) and host_spans[following][0] <= gap_start:
            opened.append(host_spans[following])
            following += 1
        opened = [span for span in opened if span[1] > gap_start]
        name = opened[-1][2] if opened else "none"
        idle_us[name] = idle_us.get(name, 0.0) + gap_end - gap_start
    dispatches = [end for _, end, name in host_spans if name == "step/dispatch"]
    per_step = []
    for k, (run_start, run_end) in enumerate(step_runs):
        row = {"device_start_ms": (run_start - window_start) / 1e3,
               "device_end_ms": (run_end - window_start) / 1e3}
        if k < len(dispatches):
            row["dispatch_end_ms"] = (dispatches[k] - window_start) / 1e3
            row["lead_ms"] = (run_start - dispatches[k]) / 1e3
            inside = [h[3] for h in fit_steps if h[1] <= dispatches[k] <= h[2]]
            row["step_num"] = inside[0] if inside else None
        per_step.append(row)
    return host_us, idle_us, per_step


def format_partition(summary: Dict) -> str:
    """One line for a log: the step and where its device time went."""
    parts = " ".join(f"{k}={v:.3f}" for k, v in summary["partition_ms"].items())
    return (f"{summary['steps']} runs of {summary['module']} on device {summary['device']}: "
            f"step {summary['step_ms']:.3f} ms, busy {summary['busy_ms']:.3f}, "
            f"idle {summary['idle_ms']:.3f}; {parts}")
