"""The step's anatomy from inside the program: the partition of a captured
step by phase, the exchange operation by operation, host spans on the
device's clock, and the counters that share their names.

On hand-made captures whose answer can be worked out on paper, on captures
recorded on the chip (``benchmark/testdata/``) against the benchmark's own
reduction of the same file, and on a CPU capture of ``Trainer.fit``."""

import json
import os
import sys
import time

import jax
import optax
import pytest

import bagua_tpu
from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
from bagua_tpu.models.mlp import init_mlp, mse_loss
from bagua_tpu.observability import cold_start
from bagua_tpu.observability import trace_analysis as ta
from bagua_tpu.observability.annotations import host_span, timed_host_span
from bagua_tpu.observability.scope_grammar import (
    FIT_STEP,
    format_host_span,
    hlo_op_labels,
    parse_host_span,
    parse_model_part,
)
from bagua_tpu.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "ci"))
from trim_capture import short_op_name, short_text, xspace_bytes  # noqa: E402

TESTDATA = os.path.join(REPO, "benchmark", "testdata")
LAYERS = [12, 16, 16, 4]
#: every counter of ``ddp.host_overhead`` but ``steps``
COUNTERS = ("pre", "lock_wait", "dispatch", "post", "build", "telemetry", "health",
            "next_batch", "loop")


# -- units --------------------------------------------------------------------


def test_owned_time_partitions_nested_and_overlapping_spans():
    # a loop [0, 100] around two body operations, then one alone
    assert ta._owned([(0, 100), (10, 30), (50, 60), (120, 130)]) == [70, 20, 10, 10]
    # two executor threads that overlap: the later start owns the overlap
    assert ta._owned([(0, 10), (5, 15)]) == [5, 10]
    # the same span twice is counted once
    assert sum(ta._owned([(1, 3), (1, 3)])) == 2
    spans = [(0, 7), (2, 4), (3, 9), (20, 21), (20, 20)]
    assert sum(ta._owned(spans)) == pytest.approx(
        ta._length(ta._merge_intervals(list(spans))))
    assert ta._owned([]) == []


def test_phase_of_reads_the_step_scope_and_autodiffs_frame():
    fwd = "jit(local_step)/shard_map/bagua_step/phase=fwd_bwd/jvp(dense)/dot_general"
    bwd = ("jit(local_step)/shard_map/bagua_step/phase=fwd_bwd/"
           "transpose(bagua_step/phase=fwd_bwd)/jvp(dense)/dot_general")
    assert ta.phase_of(fwd) == "forward" and ta.phase_of(bwd) == "backward"
    assert ta.phase_of("x/bagua_step/phase=optimizer/sub") == "optimizer"
    assert ta.phase_of("x/bagua_step/phase=sharded_update/sub") == "optimizer"
    assert ta.phase_of("x/bagua_step/phase=restack/broadcast_in_dim") == "restack"
    assert ta.phase_of("x/bagua_step/phase=algo_end/mul") == "algo_end"
    assert ta.phase_of("jit(local_step)/copy") == ta.phase_of(None) == "unattributed"


def test_operand_bytes_from_the_instructions_text():
    variadic = ("%all-reduce.73 = (bf16[1024]{0:T(1024)(128)(2,1)}, bf16[4096,1024]{1,0:T(8,128)(2,1)}) "
                "all-reduce(bf16[1024]{0:T(1024)(128)(2,1)} %fusion.1, bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} "
                "%fusion.2), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add.f32[2]")
    assert ta._operand_bytes(variadic) == 2 * 1024 + 2 * 4096 * 1024
    # an asynchronous start produces operands, results and a scalar: the operands count
    start = ("%all-reduce-start.3 = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) "
             "all-reduce-start(f32[8]{0} %x), replica_groups={}")
    assert ta._operand_bytes(start) == 32
    # cut to what it produces (benchmark/tools/trim_xplane.py), or with its operand shapes
    assert ta._operand_bytes("%psum.2379 = f32[1024,30522] all-reduce()") == 4 * 1024 * 30522
    assert ta._operand_bytes("%psum.7 = (f32[8], pred[16]) all-reduce(f32[8], pred[16])") == 48
    assert ta._operand_bytes("%ag = s4[64] all-gather(s4[16])") == 8
    assert ta._operand_bytes("jit_local_step(123)") is None
    # what the PR's trimming tool writes reads back to the same bytes
    assert short_text(variadic, keep_operands=True) == (
        "%all-reduce.73 = (bf16[1024], bf16[4096,1024]) all-reduce(bf16[1024], bf16[4096,1024])")
    assert ta._operand_bytes(short_text(variadic, keep_operands=True)) == ta._operand_bytes(variadic)
    assert short_text(variadic, keep_operands=False).endswith("all-reduce()")


def test_host_span_grammar_round_trips():
    assert format_host_span("step/dispatch") == "bagua_host/step/dispatch"
    assert parse_host_span("bagua_host/step/dispatch") == "step/dispatch"
    for other in ("bagua_fit", "bagua_host", "bagua_host/", "data", "$threading.py:323 wait", ""):
        assert parse_host_span(other) is None
    bwd = ("jit(local_step)/bagua_step/phase=fwd_bwd/transpose(jvp(x))/bagua_overlap_bwd/bucket=3/"
           "bagua_ex/algo=gradient_allreduce/bucket=3/phase=overlap/psum")
    short = short_op_name(bwd)
    assert ta.phase_of(short) == "backward"
    assert ta._exchange_label(short) == "bagua_ex/algo=gradient_allreduce/bucket=3/phase=overlap"
    assert short_op_name("jit(local_step)/copy") == ""


def test_a_timed_span_is_one_measurement_for_the_span_and_its_counter():
    totals = {"dispatch": 1.0}
    with timed_host_span("step", "dispatch", totals) as span:
        pass
    assert span.elapsed > 0 and totals["dispatch"] == pytest.approx(1.0 + span.elapsed)
    with pytest.raises(KeyError):  # a span without a counter of its name is a mistake
        with timed_host_span("step", "no_such_counter", totals):
            pass
    with host_span("fit/train_step"):  # no profiler active: a flag test
        pass


# -- a capture worked out on paper --------------------------------------------


def paper_capture(path):
    """Two steps of 100 µs on a chip's planes.  In each: forward [0, 30],
    backward [30, 60] with an asynchronous all-reduce in flight [40, 90]
    (its start [40, 41] and its done [60, 90] on the operations' line), a
    synchronous one [90, 93], the update [93, 97], idle to 100.  The batch
    maker runs [97, 98].  The host dispatched step 0 at -50 and step 1 at
    -20, and was inside ``next()`` when the device went idle."""
    fwd = "bagua_step/phase=fwd_bwd"
    bwd = "bagua_step/phase=fwd_bwd/transpose("
    ex = bwd + "/bagua_overlap_bwd/bucket=1/bagua_ex/algo=gradient_allreduce/bucket=1/phase=overlap"
    ops, asyncs, modules, host = [], [], [], []

    def us(t):
        return 1000 * (1000 + t)

    for k, base in enumerate((0, 100)):
        def op(text, start, end, op_name=None):
            ops.append((text, us(base + start), 1000 * (end - start),
                        {"op_name": op_name} if op_name else {}))

        op("%fusion.1 = f32[4] fusion()", 0, 30, fwd)
        op("%fusion.2 = f32[4] fusion()", 30, 40, bwd)
        op("%all-reduce-start.1 = (f32[4], f32[4]) all-reduce-start(f32[4])", 40, 41, ex)
        op("%fusion.3 = f32[4] fusion()", 41, 60, bwd)
        op("%all-reduce-done.1 = f32[4] all-reduce-done()", 60, 90, ex)
        op("%psum.9 = (f32[2], bf16[2]) all-reduce(f32[2], bf16[2])", 90, 93, ex)
        op("%fusion.4 = f32[4] fusion()", 93, 97, "bagua_step/phase=optimizer")
        op("%copy.5 = f32[4] copy()", 97, 98)
        asyncs.append(("%all-reduce-start.1 = (f32[4], f32[4]) all-reduce-start(f32[4])",
                       us(base + 40), 1000 * 50, {"op_name": ex}))
        modules.append(("jit_local_step(1)", us(base), 1000 * 97, {}))
        modules.append(("jit__lambda(2)", us(base + 97), 1000 * 1, {}))
        fit = -60 + 30 * k
        host.append((FIT_STEP, us(fit), 1000 * 25, {"step_num": 7 + k}))
        host.append(("bagua_host/fit/train_step", us(fit + 5), 1000 * 6, {}))
        host.append(("bagua_host/step/dispatch", us(fit + 6), 1000 * 4, {}))
    host.append(("bagua_host/fit/next_batch", us(95), 1000 * 10, {}))
    host.append(("data", us(96), 1000 * 2, {}))
    with open(path, "wb") as f:
        f.write(xspace_bytes([
            ("/device:TPU:0", [(ta._MODULES, modules), (ta._OPS, ops), (ta._ASYNC_OPS, asyncs)]),
            ("/host:CPU", [("python3", sorted(host, key=lambda h: h[1]))]),
        ]))
    return path


def test_summary_of_a_capture_worked_out_on_paper(tmp_path):
    got = ta.summarize_capture(paper_capture(str(tmp_path / "paper.xplane.pb")))
    assert got is ta.last_summary()
    assert (got["module"], got["device"], got["steps"], got["labeled"]) == (
        "jit_local_step", 0, 2, True)
    ms = pytest.approx
    assert got["step_ms"] == ms(0.097) and got["window_ms"] == ms(0.099)
    assert got["busy_ms"] == ms(0.098) and got["idle_ms"] == ms(0.001)
    assert got["idle_share"] == ms(2 / 198)
    # every operation of the step module in exactly one class
    assert got["partition_ms"] == {
        "forward": ms(0.030), "backward": ms(0.029), "exchange": ms(0.034),
        "optimizer": ms(0.004)}
    assert sum(got["partition_ms"].values()) == ms(got["step_busy_ms"])
    assert got["other_modules_ms"] == {"jit__lambda": ms(0.001)}
    assert got["step_busy_ms"] + 0.001 == ms(got["busy_ms"])
    ex = got["exchange"]
    assert ex["calls"] == 2 and ex["bytes"] == 16 + 12
    assert ex["collective_ms"] == ms(0.053)        # [40, 93]
    assert ex["exposed_ms"] == ms(0.034)           # [40, 41] and [60, 93]: [41, 60] lies under the backward
    assert ex["tail_ms"] == ms(0.033)              # the backward's last operation ends at 60
    first, second = ex["ops"]
    assert first["name"] == "all-reduce-start.1" and first["ms"] == ms(0.050)
    assert first["covered_ms"] == ms(0.019)        # [41, 60]; its own start is no cover
    assert first["label"] == "bagua_ex/algo=gradient_allreduce/bucket=1/phase=overlap"
    assert first["start_after_first_backward_ms"] == ms(0.010)
    assert first["start_after_last_backward_ms"] == ms(-0.020) and not first["after_backward"]
    assert second["name"] == "psum.9" and second["bytes"] == 12 and second["after_backward"]
    assert second["start_after_last_backward_ms"] == ms(0.030) and second["covered_ms"] == 0
    # the host's side, per step
    assert got["host_spans_ms"] == {
        "fit/next_batch": ms(0.005), "fit/train_step": ms(0.006), "step/dispatch": ms(0.004)}
    assert got["idle_by_host_span_ms"] == {"fit/next_batch": ms(0.001)}
    assert [(r["step_num"], r["dispatch_end_ms"], r["device_start_ms"], r["device_end_ms"],
             r["lead_ms"]) for r in got["per_step"]] == [
        (7, ms(-0.050), ms(0.0), ms(0.097), ms(0.050)),
        (8, ms(-0.020), ms(0.100), ms(0.197), ms(0.120))]
    assert [u["name"] for u in got["unattributed_top"]] == []
    assert "forward=0.030" in ta.format_partition(got)
    # without a device plane of that number there is nothing to summarize
    assert ta.summarize_capture(str(tmp_path / "paper.xplane.pb"), device=3) is None


# -- captures recorded on the chip --------------------------------------------

LABELLED = "bert-large.dp4.device0.step1.labelled"
UNLABELLED = "bert-large.dp4.device0.step1"


def recorded(name):
    path = os.path.join(TESTDATA, name + ".xplane.pb")
    with open(os.path.join(TESTDATA, name + ".expected.json")) as f:
        return path, json.load(f)


def agrees_with_the_benchmarks_reduction(got, expected):
    """The two reducers on one file: collective, exposed and busy time."""
    ms = pytest.approx
    steps = expected["steps"]
    assert got["steps"] == steps
    assert got["exchange"]["collective_ms"] == ms(1e3 * expected["collective_s"] / steps, rel=1e-6)
    assert got["exchange"]["exposed_ms"] == ms(
        1e3 * expected["exposed_collective_s"] / steps, rel=1e-6)
    assert got["busy_ms"] == ms(1e3 * expected["device0_busy_s"] / steps, rel=1e-6)
    assert got["partition_ms"]["exchange"] == ms(got["exchange"]["collective_ms"], rel=1e-6)
    assert sum(got["partition_ms"].values()) + sum(got["other_modules_ms"].values()) == ms(
        got["busy_ms"], rel=1e-6)


def test_unlabelled_capture_from_the_chip_agrees_with_the_benchmarks_reduction():
    path, expected = recorded(UNLABELLED)
    got = ta.summarize_capture(path)
    agrees_with_the_benchmarks_reduction(got, expected)
    # no labels there: everything but the exchange is unattributed, all of it exposed
    assert set(got["partition_ms"]) == {"exchange", "unattributed"} and not got["labeled"]
    assert got["exchange"]["collective_ms"] == pytest.approx(14.929, abs=5e-4)
    assert got["exchange"]["exposed_ms"] == got["exchange"]["collective_ms"]
    assert got["exchange"]["calls"] == 8 and got["exchange"]["tail_ms"] is None


def test_labelled_capture_from_the_chip_is_pinned_and_agrees_with_the_benchmark():
    path, expected = recorded(LABELLED)
    got = ta.summarize_capture(path)
    agrees_with_the_benchmarks_reduction(got, expected)
    with open(os.path.join(TESTDATA, LABELLED + ".summary.json")) as f:
        pinned = json.load(f)
    assert got["partition_ms"] == pytest.approx(pinned["partition_ms"], rel=1e-9)
    for key in ("calls", "bytes", "collective_ms", "exposed_ms", "tail_ms"):
        assert got["exchange"][key] == pytest.approx(pinned["exchange"][key], rel=1e-9)
    assert [r["name"] for r in got["exchange"]["ops"]] == [
        r["name"] for r in pinned["exchange"]["ops"]]
    assert [r["label"] for r in got["exchange"]["ops"]] == [
        r["label"] for r in pinned["exchange"]["ops"]]
    assert got["per_step"] == [pytest.approx(row, rel=1e-9) for row in pinned["per_step"]]
    assert got["host_spans_ms"] == pytest.approx(pinned["host_spans_ms"], rel=1e-9)
    # what the capture says, whatever the numbers: a step by phase, the
    # exchange inside the backward pass and exposed
    assert {"forward", "backward", "exchange", "optimizer", "restack"} <= set(got["partition_ms"])
    assert got["partition_ms"]["unattributed"] < 0.1 * got["step_busy_ms"]
    assert got["exchange"]["calls"] == 8
    assert all(r["label"] and r["label"].startswith("bagua_ex/") for r in got["exchange"]["ops"])
    assert got["exchange"]["tail_ms"] < 0.5 * got["exchange"]["collective_ms"]
    assert got["per_step"][0]["step_num"] is not None and got["per_step"][0]["lead_ms"] > 0
    assert {"fit/next_batch", "fit/train_step", "step/pre", "step/dispatch",
            "step/post"} <= set(got["host_spans_ms"])


# -- Trainer.fit on the CPU ---------------------------------------------------

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles = []


def count_backend_compiles():
    """Cache loads included.  A listener cannot be removed, so one serves the
    process, registered by the first test that needs it."""
    if not _compiles:
        _compiles.append("listening")
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: _compiles.append(event)
            if event == BACKEND_COMPILE else None)


def batches(n):
    import numpy as np

    rng = np.random.RandomState(0)
    for _ in range(n):
        yield (rng.randn(32, LAYERS[0]).astype(np.float32),
               rng.randn(32, LAYERS[-1]).astype(np.float32))


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """One ``Trainer.fit`` of eight steps on four CPU devices that captures
    iterations 3, 4 and 5."""
    count_backend_compiles()
    group = bagua_tpu.init_process_group(devices=jax.devices()[:4])
    profile_dir = str(tmp_path_factory.mktemp("capture"))
    trainer = Trainer(mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                      process_group=group, watchdog_timeout_s=0,
                      profile_dir=profile_dir, profile_steps=(3, 6))
    marks = {}
    start = trainer._start_capture

    def marked_start(state):
        start(state)
        marks["at_start"] = len(_compiles)

    trainer._start_capture = marked_start
    state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
    trainer.ddp.host_overhead_snapshot(reset=True)
    state = trainer.fit(state, batches(8), log_every=0)
    marks["at_return"] = len(_compiles)
    # a profiling Trainer keys the process's compiles with their metadata, so that
    # no executable cached for a program with other labels answers for its own
    marks["metadata_in_key"] = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    yield trainer, profile_dir, marks
    trainer.close()


def test_cpu_capture_of_fit_holds_the_step_annotations_and_nested_host_spans(traced_fit):
    trainer, profile_dir, _ = traced_fit
    host = ta._read_capture(profile_dir)["host"]
    fits = [h for h in host if h[0] == FIT_STEP]
    assert [h[3] for h in fits] == [3, 4, 5]  # one per captured iteration, with its step_num
    named = {}
    for name, start, end, _ in host:
        named.setdefault(name, []).append((start, end))
    for name in ("fit/train_step", "step/pre", "step/dispatch", "step/post"):
        assert len(named[format_host_span(name)]) == 3, name
    # the capture begins with the first iteration's batch in hand
    assert len(named["bagua_host/fit/next_batch"]) == 2
    assert all(fs <= start and end <= fe for (start, end), (_, fs, fe, _) in zip(
        named["bagua_host/fit/next_batch"], fits[1:]))
    # dispatch inside train_step inside the iteration
    for (ds, de), (ts, te), (_, fs, fe, _) in zip(
            named["bagua_host/step/dispatch"], named["bagua_host/fit/train_step"], fits):
        assert fs <= ts <= ds and de <= te <= fe
    assert format_host_span("fit/capture") in named  # the drain before the stop
    # nothing built a step inside the capture, and there is no hub or monitor
    assert not {"bagua_host/step/build", "bagua_host/step/telemetry",
                "bagua_host/step/health"} & set(named)


def test_every_counter_of_the_table_is_in_the_snapshot_and_is_reset(traced_fit):
    trainer = traced_fit[0]
    snapshot = trainer.ddp.host_overhead_snapshot(reset=True)
    assert {f"{c}_ms_per_step" for c in COUNTERS} <= set(snapshot)
    assert snapshot["steps"] == 8
    for counter in ("pre", "dispatch", "post", "build", "next_batch", "loop"):
        assert snapshot[f"{counter}_ms_per_step"] > 0, counter
    for counter in ("lock_wait", "telemetry", "health"):  # nothing of the kind attached
        assert snapshot[f"{counter}_ms_per_step"] == 0, counter
    again = trainer.ddp.host_overhead_snapshot()
    assert all(again[f"{c}_ms_per_step"] == 0 for c in COUNTERS) and again["steps"] == 1
    assert set(trainer.ddp.host_overhead) == set(COUNTERS) | {"steps"}


def test_fit_reduces_its_capture_and_compiles_nothing_inside_it(traced_fit):
    trainer, profile_dir, marks = traced_fit
    assert marks["at_return"] == marks["at_start"]  # no backend compile, cache loads included
    assert marks["metadata_in_key"]
    summary = trainer.profile_summary
    assert summary is not None and summary["steps"] == 3 and summary["labeled"]
    assert summary["module"] == "jit_local_step"
    assert sum(summary["partition_ms"].values()) == pytest.approx(summary["step_busy_ms"])
    assert {"forward", "backward", "exchange"} <= set(summary["partition_ms"])
    assert [row["step_num"] for row in summary["per_step"]] == [3, 4, 5]
    assert all({"dispatch_end_ms", "device_start_ms", "device_end_ms", "lead_ms"} <= set(row)
               for row in summary["per_step"])
    assert set(summary["idle_by_host_span_ms"]) <= {"none"} | set(summary["host_spans_ms"])
    # the step's text lies beside the capture for ci/analyze_trace.py, and gives the same
    with open(os.path.join(profile_dir, ta.STEP_TEXT_FILE)) as f:
        text = f.read()
    assert text == trainer.ddp.step_text()
    again = ta.summarize_capture(profile_dir, hlo_text=text)
    assert again["partition_ms"] == pytest.approx(summary["partition_ms"])


def test_a_trainer_without_profile_dir_keeps_no_text_and_no_summary(group):
    trainer = Trainer(mse_loss, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                      process_group=group, watchdog_timeout_s=0)
    try:
        began = time.perf_counter()
        state = trainer.init_state(init_mlp(jax.random.PRNGKey(0), LAYERS))
        trainer.fit(state, batches(3), log_every=0)
        assert not trainer.ddp.keep_step_text and trainer.ddp.step_text() is None
        # ... and none was made: no text span on the process's cold record
        assert not [e for e in cold_start.cold_events()
                    if e.name == cold_start.TEXT_SPAN and e.start >= began]
        assert trainer.profile_summary is None
    finally:
        trainer.close()


def test_join_table_reads_an_instruction_printed_over_several_lines():
    """A Pallas kernel's custom call prints its ``kernel_metadata`` over
    several lines and its ``op_name`` after them (the splash attention
    kernels on the chip, PR 30): the label belongs to the instruction begun
    last, and its neighbours keep theirs."""
    text = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/bagua_step/phase=optimizer/mul"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %splash_mha_fwd_residuals.5 = (f32[8]{0}, bf16[8]{0:T(8,128)(2,1)}) custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024, \\"block_kv\\": 1024}"
}}, metadata={op_name="jit(step)/bagua_step/phase=fwd_bwd/jvp(m)/bagua_model/part=attn_core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=8}, backend_config={}
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%splash_mha_fwd_residuals.5), index=0
  ROOT %fusion.1 = f32[8]{0} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/bagua_step/phase=optimizer/mul"}
}
"""
    module, labels = hlo_op_labels(text)
    assert module == "jit_step"
    assert set(labels) == {"multiply.3", "splash_mha_fwd_residuals.5", "fusion.1"}
    assert ta.phase_of(labels["splash_mha_fwd_residuals.5"]) == "forward"
    assert parse_model_part(labels["splash_mha_fwd_residuals.5"]) == "attn_core"
    assert ta.phase_of(labels["fusion.1"]) == "optimizer"
    assert dict(ta._HLO_OPCODE.findall(text))["splash_mha_fwd_residuals.5"] == "custom-call"


def test_compiled_step_of_four_devices_carries_every_phase_and_labels_every_collective(traced_fit):
    trainer = traced_fit[0]
    text = trainer.ddp.step_text()
    _, labels = hlo_op_labels(text)
    phases = {ta.phase_of(op_name) for op_name in labels.values()}
    assert {"forward", "backward", "optimizer"} <= phases
    collectives = [name for name, opcode in ta._HLO_OPCODE.findall(text)
                   if opcode.startswith(ta.COLLECTIVE_OPS)]
    assert collectives
    for name in collectives:
        assert ta._exchange_label(labels.get(name)), name
    # XLA:CPU folds the restack's reshapes away; they are in the program it was given
    # (the chip keeps them as copies: 0.55 ms of BERT-Large's step on one chip)
    state = trainer.ddp.state_template()
    batch = next(batches(1))
    fn = trainer.ddp._build_step(trainer.ddp.last_variant)
    given = fn.lower(state, batch).as_text(debug_info=True)
    assert "bagua_step/phase=restack/slice" in given
    assert "bagua_step/phase=restack/broadcast_in_dim" in given or (
        "bagua_step/phase=restack/reshape" in given)
