"""The one scope-label grammar: formatters + parsers shared by every layer.

Every subsystem that names a collective speaks this grammar — the
``jax.named_scope`` frames emitted at trace time
(:mod:`bagua_tpu.observability.annotations`), the flight recorder's ring
records (``ddp._flight_finalize`` renders labels with
:func:`format_exchange_label`), the device-trace joiner
(:mod:`bagua_tpu.observability.trace_analysis` resolves HLO ``op_name``
metadata through :func:`hlo_op_labels`), and the static verifier
(:mod:`bagua_tpu.analysis` parses jaxpr ``name_stack`` strings).  Keeping
one module as the source of truth is what lets the verifier's *predicted*
program and the recorder's *captured* program join record-for-record on the
label key — a private copy of a regex in any one consumer would silently
fork the grammar.

The three label forms::

    bagua_ex/algo=gradient_allreduce/bucket=3/phase=overlap   (bucket exchanges)
    bagua_ex/axis=tp/phase=rs_ring                            (model-parallel)
    bagua_step/phase=optimizer                                (engine step phases)
    bagua_model/part=attn_core                                (parts of a model)
    bagua_model/pass=2                                        (passes of a looped stack)

and, on the host's side of the same capture, the spans the fit loop and the
engine open around their own work (``jax.profiler.TraceAnnotation``, see
:func:`~bagua_tpu.observability.annotations.host_span`)::

    bagua_host/step/dispatch                                  (host spans)

plus the quantized-ring sub-scopes nested *inside* a bucket-exchange frame
(``qr8_quant``, ``qr8_hop3``, ``qr4_ag`` — see
:mod:`bagua_tpu.kernels.quantized_ring`), the overlap backward anchor
``bagua_overlap_bwd/bucket=<i>`` (:mod:`bagua_tpu.bucket`), and the
bounded-staleness frame ``bagua_stale/tau=<k>`` wrapping every exchange a
stale-sync/gossip algorithm issues — the sanction marker the static
verifier's taint analysis keys off (a rank-conditional collective inside a
stale frame is bounded-by-construction, not a divergence bug).

Field separators are ``/`` (the scope-nesting separator, which XLA joins
verbatim into ``op_name``) and ``=``; characters like ``@`` are truncated
by the MLIR location plumbing and must not appear in scope names.
"""

import re
from typing import Dict, Optional, Tuple

__all__ = [
    "EXCHANGE_PREFIX",
    "STEP_PREFIX",
    "STALE_PREFIX",
    "MODEL_PREFIX",
    "HOST_PREFIX",
    "FIT_STEP",
    "EXCHANGE_RE",
    "STEP_RE",
    "MP_RE",
    "QR_RE",
    "OVERLAP_BWD_RE",
    "STALE_RE",
    "MODEL_RE",
    "PASS_RE",
    "format_exchange_label",
    "format_mp_label",
    "format_step_label",
    "format_stale_scope",
    "format_model_label",
    "format_pass_label",
    "format_host_span",
    "parse_exchange_label",
    "parse_mp_label",
    "parse_step_phase",
    "parse_qr_scope",
    "parse_overlap_bwd",
    "parse_stale_scope",
    "parse_model_part",
    "parse_model_pass",
    "parse_host_span",
    "hlo_op_labels",
]

#: scope-name prefixes (kept short: every annotated HLO op carries them)
EXCHANGE_PREFIX = "bagua_ex"
STEP_PREFIX = "bagua_step"
STALE_PREFIX = "bagua_stale"
#: parts of a model's forward pass (and, through autodiff, of its backward
#: pass): a model whose step is made of unlike parts names them
MODEL_PREFIX = "bagua_model"
#: host spans (profiler annotations, not HLO metadata)
HOST_PREFIX = "bagua_host"
#: the ``StepTraceAnnotation`` around one iteration of ``Trainer.fit``
FIT_STEP = "bagua_fit"

EXCHANGE_RE = re.compile(
    EXCHANGE_PREFIX + r"/algo=(?P<algo>[^/]+)/bucket=(?P<bucket>\d+)/phase=(?P<phase>[^/\"]+)"
)
STEP_RE = re.compile(STEP_PREFIX + r"/phase=(?P<phase>[^/\"]+)")
MP_RE = re.compile(
    EXCHANGE_PREFIX + r"/axis=(?P<axis>[^/=]+)/phase=(?P<phase>[^/\"]+)"
)
#: quantized-ring sub-scopes (nested inside a bucket-exchange frame)
QR_RE = re.compile(r"qr(?P<bits>\d+)_(?P<stage>quant|ag|hop(?P<hop>\d+))")
#: the custom_vjp backward anchor wrapping each bucket's overlap exchange
OVERLAP_BWD_RE = re.compile(r"bagua_overlap_bwd/bucket=(?P<bucket>\d+)")
#: the bounded-staleness sanction frame (τ = the staleness bound the
#: algorithm was compiled at)
STALE_RE = re.compile(STALE_PREFIX + r"/tau=(?P<tau>\d+)")
# (a scope open where autodiff begins is written inside its frame: ``jvp(bagua_model/part=x)``)
MODEL_RE = re.compile(MODEL_PREFIX + r"/part=(?P<part>[^/\")]+)")
#: which run of a stack whose layers run several times with shared weights
PASS_RE = re.compile(MODEL_PREFIX + r"/pass=(?P<pass>\d+)")


# -- formatters (the single way a label string is ever built) -----------------


def format_exchange_label(algo: str, bucket_idx, phase: str) -> str:
    """Render one bucket-exchange label; the inverse of
    :func:`parse_exchange_label` and the exact string both
    ``annotations.bucket_scope`` and the flight recorder's record templates
    carry."""
    return f"{EXCHANGE_PREFIX}/algo={algo}/bucket={int(bucket_idx)}/phase={phase}"


def format_mp_label(axis: str, phase: str) -> str:
    return f"{EXCHANGE_PREFIX}/axis={axis}/phase={phase}"


def format_step_label(phase: str) -> str:
    return f"{STEP_PREFIX}/phase={phase}"


def format_stale_scope(tau) -> str:
    """Render the bounded-staleness frame a stale-sync/gossip exchange is
    traced under — the marker :func:`parse_stale_scope` (and through it the
    static verifier's sanction) recovers from the jaxpr name stack."""
    return f"{STALE_PREFIX}/tau={int(tau)}"


def format_model_label(part: str) -> str:
    return f"{MODEL_PREFIX}/part={part}"


def format_pass_label(index) -> str:
    return f"{MODEL_PREFIX}/pass={int(index)}"


def format_host_span(name: str) -> str:
    """``bagua_host/<name>``; ``name`` is ``<where>/<what>`` (``fit/next_batch``,
    ``step/dispatch``)."""
    return f"{HOST_PREFIX}/{name}"


# -- parsers ------------------------------------------------------------------


def parse_exchange_label(op_name: str) -> Optional[Dict]:
    """Extract ``{algo, bucket, phase}`` from any string carrying a
    bucket-exchange frame (HLO ``op_name`` metadata, a jaxpr ``name_stack``,
    a flight-recorder label); None when no frame is present."""
    m = EXCHANGE_RE.search(op_name or "")
    if not m:
        return None
    return {"algo": m.group("algo"), "bucket": int(m.group("bucket")), "phase": m.group("phase")}


def parse_mp_label(op_name: str) -> Optional[Dict]:
    """Extract ``{axis, phase}`` from a model-parallel exchange frame; None
    for unlabeled ops (bucket-exchange labels use ``algo=``/``bucket=``
    fields and never match)."""
    m = MP_RE.search(op_name or "")
    if not m:
        return None
    return {"axis": m.group("axis"), "phase": m.group("phase")}


def parse_step_phase(op_name: str) -> Optional[str]:
    """The engine step phase an op was traced under, if labeled."""
    m = STEP_RE.search(op_name or "")
    return m.group("phase") if m else None


def parse_qr_scope(op_name: str) -> Optional[Dict]:
    """Extract ``{bits, stage, hop}`` from a quantized-ring sub-scope
    (``stage`` is ``"quant"``, ``"hop"`` or ``"ag"``; ``hop`` is the 1-based
    hop index for hop frames, else None)."""
    m = QR_RE.search(op_name or "")
    if not m:
        return None
    stage = m.group("stage")
    hop = m.group("hop")
    return {
        "bits": int(m.group("bits")),
        "stage": "hop" if hop is not None else stage,
        "hop": int(hop) if hop is not None else None,
    }


def parse_overlap_bwd(op_name: str) -> Optional[int]:
    """Bucket index of a ``bagua_overlap_bwd`` backward anchor, if present."""
    m = OVERLAP_BWD_RE.search(op_name or "")
    return int(m.group("bucket")) if m else None


def parse_stale_scope(op_name: str) -> Optional[int]:
    """The staleness bound τ of a ``bagua_stale`` frame, if present."""
    m = STALE_RE.search(op_name or "")
    return int(m.group("tau")) if m else None


def parse_model_part(op_name: str) -> Optional[str]:
    """The part of the model an op was traced under, if labeled: the
    innermost ``bagua_model`` frame of its ``op_name``."""
    parts = MODEL_RE.findall(op_name or "")
    return parts[-1] if parts else None


def parse_model_pass(op_name: str) -> Optional[int]:
    """The pass of a looped stack an op was traced under, if labeled: the
    innermost ``bagua_model/pass=`` frame of its ``op_name``."""
    passes = PASS_RE.findall(op_name or "")
    return int(passes[-1]) if passes else None


def parse_host_span(event_name: str) -> Optional[str]:
    """``step/dispatch`` from ``bagua_host/step/dispatch``; None for any
    other event of the host's lines."""
    head, _, name = (event_name or "").partition("/")
    return name if head == HOST_PREFIX and name else None


# -- the HLO join table -------------------------------------------------------

# an instruction's start, or an ``op_name`` of the instruction begun last.  A
# Pallas kernel's custom call prints its ``kernel_metadata`` over several lines
# and its ``metadata={op_name=...}`` after them, so the two are not on one line
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) = |metadata=\{[^}]*op_name=\"([^\"]*)\"", re.MULTILINE)
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.MULTILINE)


def hlo_op_labels(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module_name, {instruction_name: op_name_metadata})`` from compiled
    HLO text — the join table between trace events and named-scope labels."""
    m = _HLO_MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    labels, instruction = {}, None
    for name, op_name in _HLO_INSTR.findall(hlo_text):
        if name:
            instruction = name
        elif instruction is not None:
            labels[instruction] = op_name
    return module, labels
