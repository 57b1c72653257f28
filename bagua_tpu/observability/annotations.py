"""In-graph labeling: attribute device-trace ops to buckets and step phases.

The overlap relaxations only pay off if each bucket's collective really
rides the backward pass — and the only ground truth is the device trace.
XLA carries a per-instruction ``op_name`` metadata string assembled from
``jax.named_scope`` frames, and the events of a profiler capture
(``.xplane.pb``) can be joined back to it through the instruction name and
the compiled module's text.  These helpers emit a *parseable* scope grammar so
:mod:`bagua_tpu.observability.trace_analysis` can attribute every
collective span to its ``algo``/``bucket``/``phase`` (the transparent
fine-grained tracking of T3, arXiv:2401.16677; the reference shipped the
host-side analog as OTel spans in ``bagua-opentelemetry``):

    bagua_ex/algo=gradient_allreduce/bucket=3/phase=overlap   (bucket exchanges)
    bagua_ex/axis=tp/phase=rs_ring                             (model-parallel)
    bagua_step/phase=optimizer                                 (step phases)
    bagua_model/part=attn_core                                 (parts of a model)
    bagua_model/pass=2                                         (passes of a looped stack)
    bagua_host/step/dispatch                                   (host spans)

The second form labels *model-parallel* exchanges — the tensor-parallel
``psum``/ring ``ppermute``s and the MoE dispatch/combine all-to-alls — which
have no bucket index: they are keyed by the logical parallelism axis (``tp``
or ``ep``) plus a phase naming the exchange (``row_psum``, ``ag_ring``,
``rs_ring``, ``row_allgather``, ``dispatch``, ``combine``).  The trace
analyzer aggregates them into per-scope ``measured_overlap_frac`` rows.

The last form is not HLO metadata but a ``jax.profiler.TraceAnnotation``
(:func:`host_span`): the fit loop and the engine put their own host work on
the capture's clock, so an idle gap of the device can be put down to what
the host was doing when it began.  With no profiler active it is a flag test.

``named_scope`` only decorates metadata — it never changes the traced
computation, so annotated and unannotated steps are bitwise-identical and
the scopes stay on unconditionally.

The grammar itself — prefixes, regexes, formatters and parsers — lives in
:mod:`bagua_tpu.observability.scope_grammar`, shared with the device-trace
joiner, the flight recorder's record templates and the static verifier
(:mod:`bagua_tpu.analysis`); this module re-exports the parsers and adds
the ``jax.named_scope`` factories.
"""

import time

import jax

from bagua_tpu.observability.scope_grammar import (
    EXCHANGE_PREFIX,
    FIT_STEP,
    HOST_PREFIX,
    MODEL_PREFIX,
    STEP_PREFIX,
    format_exchange_label,
    format_host_span,
    format_model_label,
    format_mp_label,
    format_pass_label,
    format_step_label,
    parse_exchange_label,
    parse_host_span,
    parse_model_part,
    parse_model_pass,
    parse_mp_label,
    parse_step_phase,
)

# Back-compat aliases for the pre-hoist private names.
from bagua_tpu.observability.scope_grammar import EXCHANGE_RE as _EXCHANGE_RE  # noqa: F401
from bagua_tpu.observability.scope_grammar import MP_RE as _MP_RE  # noqa: F401
from bagua_tpu.observability.scope_grammar import STEP_RE as _STEP_RE  # noqa: F401

__all__ = [
    "EXCHANGE_PREFIX",
    "STEP_PREFIX",
    "HOST_PREFIX",
    "MODEL_PREFIX",
    "bucket_scope",
    "step_scope",
    "mp_scope",
    "model_scope",
    "pass_scope",
    "host_span",
    "timed_host_span",
    "fit_step_span",
    "parse_exchange_label",
    "parse_host_span",
    "parse_model_part",
    "parse_model_pass",
    "parse_mp_label",
    "parse_step_phase",
]


def bucket_scope(algo: str, bucket_idx, phase: str):
    """Named scope labeling one bucket's exchange ops.

    ``algo`` is the algorithm's registry-style name, ``phase`` distinguishes
    the monolithic tail exchange (``mono``) from the backward-anchored one
    (``overlap``).  Use as a context manager around the traced exchange."""
    return jax.named_scope(format_exchange_label(algo, bucket_idx, phase))


def step_scope(phase: str):
    """Named scope labeling one engine phase of the train step
    (``fwd_bwd``, ``optimizer``, ``algo_start``, ``algo_end``,
    ``finalize``...)."""
    return jax.named_scope(format_step_label(phase))


def mp_scope(axis: str, phase: str):
    """Named scope labeling one model-parallel exchange.

    ``axis`` is the *logical* parallelism scope — ``"tp"`` for tensor-parallel
    exchanges, ``"ep"`` for expert-parallel ones — not the mesh axis name
    (which is deployment-specific and may be a tuple).  ``phase`` names the
    exchange within the scope (``row_psum``, ``ag_ring``, ``rs_ring``,
    ``row_allgather``, ``col_allgather``, ``dispatch``, ``combine``).  Use as
    a context manager around the collective, exactly like
    :func:`bucket_scope`."""
    return jax.named_scope(format_mp_label(axis, phase))


def model_scope(part: str):
    """Named scope labeling one part of a model's forward pass
    (``attn_proj``, ``attn_core``, ``moe_route``, ``moe_dispatch``,
    ``moe_experts``, ``moe_combine``, ``moe_shared``, ``dense_mlp``,
    ``head``, ``embed``; in ``models/lfm2_moe.py`` also ``conv_proj``, the two products
    of a gated short convolution, and ``conv_core``, the gates and taps
    between them; in ``models/smallthinker_moe.py`` ``attn_window_core``, the
    core of a layer whose mask has a window, beside ``attn_core`` for the
    layers whose mask has none; in ``models/ouro.py`` ``exit_gate``, the gate's
    product after every pass, the exit distribution, the weighted sum of the
    exits' losses and the entropy; in ``models/nemotron_h.py`` ``ssm_proj``, a
    Mamba-2 mixer's two products, ``ssm_conv``, its depthwise causal
    convolution with the bias and SiLU, ``ssm_core``, the chunked scan, the
    ``D`` term, the gate and the group norm, and ``moe_latent``, the two
    projections between the hidden and the experts' latent width; in
    ``models/laguna.py`` ``attn_gate``, the per-head output gate between the
    core and ``W_o``: its product, sigmoid and multiplication; in
    ``models/solar_open2.py`` ``kda_proj``, a KDA mixer's four wide products
    and four narrow ones, ``kda_conv``, its three depthwise causal convolutions
    with SiLU, ``kda_core``, the L2 norms, the decay, ``beta`` and the chunked
    delta rule, all of it built again backward, and ``kda_gate_norm``, the head
    norm and its gate, beside ``attn_gate`` at a value a head column).  Any name
    is a part: the summary keeps what it finds.
    Autodiff carries the frame into the backward pass's ops, and
    ``jax.checkpoint`` into those it runs again there, so the device trace
    gives each part's forward, backward and recomputed time together
    (``model_part_ms`` of ``trace_analysis.summarize_capture``)."""
    return jax.named_scope(format_model_label(part))


def pass_scope(index: int):
    """Named scope ``bagua_model/pass=<index>`` around one run of a stack
    whose layers run several times with the same weights, and around that
    run's exit (``models/ouro.py``; passes count from 1).  The parts' scopes
    nest inside it, so an operation has a part and a pass, and the summary
    gives ``model_pass_ms`` beside ``model_part_ms``."""
    return jax.named_scope(format_pass_label(index))


def host_span(name: str):
    """Profiler annotation ``bagua_host/<name>`` around a stretch of the
    host's own work (``fit/next_batch``, ``step/dispatch``): it lands on the
    host's line of the capture, on the device trace's clock."""
    return jax.profiler.TraceAnnotation(format_host_span(name))


class timed_host_span:
    """``host_span(f"{where}/{key}")`` that also adds the ``perf_counter``
    time it was open to ``totals[key]`` and leaves it in ``elapsed`` (and the
    reading it opened at in ``began``): the span in the capture and the
    counter of the same name are one measurement and cannot disagree.
    :class:`~bagua_tpu.observability.cold_start.cold_host_span` is the same
    span around what happens once or rarely."""

    __slots__ = ("_span", "_totals", "_key", "began", "elapsed")

    def __init__(self, where: str, key: str, totals: dict):
        self._span = host_span(f"{where}/{key}")
        self._totals, self._key = totals, key
        self.elapsed = 0.0

    def __enter__(self):
        self._span.__enter__()
        self.began = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self.began
        self._totals[self._key] += self.elapsed
        self._span.__exit__(*exc)
        return False


def fit_step_span(step_num: int):
    """The ``bagua_fit`` step annotation around one iteration of
    ``Trainer.fit``; the capture keeps ``step_num`` as a statistic."""
    return jax.profiler.StepTraceAnnotation(FIT_STEP, step_num=step_num)
