"""The ``smallthinker-21ba3b`` configuration and its cell: the stated precision
against the control at the toy limits, runs whose timed path is broken
underneath (the state kept, half the batch, the window left out, 8-bit
weights), the six readers of the model's parts on the summary of a traced run
on the chip, the adapter's operation counts worked out on paper, and what the
configuration's file states of the cut."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest
from test_benchmark_correct import drive, toy_run
from test_benchmark_run import run_cell

CELL = "smallthinker-21ba3b.dp1-s8192"
CONFIG = "smallthinker-21ba3b"
LFM2_CELL = "lfm2-8b-a1b.dp1-s8192"
BENCH = manifest.benchmark_json()
READERS = ["st_attention_ms_per_step", "st_window_attention_core_roofline_pct",
           "st_full_attention_core_roofline_pct", "st_moe_routing_ms_per_step",
           "st_moe_experts_ms_per_step", "st_moe_experts_roofline_pct"]
LFM2_READERS = ["conv_mixer_ms_per_step", "gqa_attention_ms_per_step",
                "gqa_attention_core_roofline_pct", "lfm2_moe_routing_ms_per_step",
                "lfm2_moe_experts_ms_per_step", "lfm2_moe_experts_roofline_pct"]
PUBLISHED_LAYOUT = [0, 1, 1, 1] * 13


# -- correct ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [2_400_000_011, 17, 2**31 + 5])
def test_the_stated_precision_passes_and_the_control_does_not(seed):
    cell, run = toy_run(CELL, seed)
    ref = run.reference()
    sound = run.numbers(ref)
    passed, lines = check.verdict(sound, cell.tolerances)
    assert passed, lines
    control, _ = check.compare(*run.reference(control=True), *ref, head=cell.adapter.HEAD_LEAF)
    passed, lines = check.verdict(control, cell.tolerances)
    assert not passed, lines
    # the numbers that separate the precisions: the output matrix's gradient and the whole one
    for name in ("head_rel_err", "grad_rel_err"):
        limit = cell.tolerances[name]["limit"]
        assert control[name] > limit > sound[name], name


def test_the_program_with_8_bit_weights_does_not_pass():
    def patch(adapter):
        build = adapter.build_loss
        adapter.build_loss = lambda sizes: check.lower_precision(build(sizes))

    cell, run = toy_run(CELL, 2_400_000_011, patch_adapter=patch)
    passed, lines = check.verdict(run.numbers(run.reference()), cell.tolerances)
    assert not passed, lines


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    from bagua_tpu.ddp import DistributedDataParallel

    real = DistributedDataParallel.train_step

    def frozen(self, state, batch):
        kept = jax.tree.map(jnp.copy, state.params)
        new_state, losses = real(self, state, batch)
        return new_state._replace(params=kept), losses

    monkeypatch.setattr(DistributedDataParallel, "train_step", frozen)
    result = drive(capsys, CELL)
    assert result["correct"] is False
    assert result["checks"]["update_norm_gap"] == pytest.approx(1.0)
    assert result["checks"]["grad_rel_err"] == pytest.approx(1.0)


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch, capsys):
    from bagua_tpu.ddp import DistributedDataParallel

    real = DistributedDataParallel.train_step

    def half(self, state, batch):
        rows = jax.tree.leaves(batch)[0].shape[0]
        batch = jax.tree.map(lambda x: jnp.concatenate([x[:rows // 2]] * 2), jax.device_get(batch))
        return real(self, state, self.shard_batch(batch))

    monkeypatch.setattr(DistributedDataParallel, "train_step", half)
    result = drive(capsys, CELL)
    assert result["correct"] is False and result["checks"]["grad_rel_err"] > 0.3


def test_a_window_left_out_is_not_correct(monkeypatch, capsys):
    """The timed path with every layer attending to all earlier keys: the
    mask is part of the arithmetic ``correct`` holds the program to."""
    from bagua_tpu.models import smallthinker_moe

    real = smallthinker_moe.causal_attention
    monkeypatch.setattr(smallthinker_moe, "causal_attention",
                        lambda q, k, v, scale, window=None: real(q, k, v, scale))
    result = drive(capsys, CELL)
    assert result["correct"] is False
    # by the windowed layer's output matrix, whose gradient is the attended values' own: the
    # seeded stream is a token's own (the embedding at unit variance), so random keys by the
    # dozen average to little, and the whole gradient moves by less than its limit
    limits = manifest.load_cell(CELL, dry=True).tolerances
    assert result["checks"]["update_norm_gap"] > limits["update_norm_gap"]["limit"]
    assert result["checks"]["grad_rel_err"] > 1.5 * limits["grad_rel_err"]["sound_max"]


def test_an_unbroken_run_in_this_process_is_correct(capsys):
    assert drive(capsys, CELL)["correct"] is True


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 36" in limits["_readings"]
        numbers = {k: v for k, v in limits.items() if k != "_readings"}
        assert set(numbers) == {"loss_gap", "grad_rel_err", "head_rel_err", "grad_norm_gap",
                                "update_norm_gap"}
        for name, record in numbers.items():
            assert record["limit"] >= 3 * record["sound_max"] * 0.99, (group, name)  # three digits kept
        assert any(r["control_fails_it"] for r in numbers.values()), group
        assert numbers["head_rel_err"]["control_fails_it"], group
    assert ("TPU v5 lite x1" in detail["tolerances"]["_readings"]
            and "cpu x1" in detail["toy_tolerances"]["_readings"])


@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_prints_the_contracts_last_line_at_a_large_seed(trace):
    proc = run_cell("--workload", CELL, "--seed", str(2**31 + 1_000_003), "--seconds", "2",
                    "--trace", str(trace), "--dry-run")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["dry_run"] is True
    assert result["workload"] == CELL and result["device"]["count"] == 1
    wanted = {m["name"] for m in (manifest.load_cell(CELL).per_layer if trace
                                  else manifest.load_cell(CELL).end_to_end)}
    assert set(result["metrics"]) <= wanted
    if not trace:
        assert set(result["metrics"]) == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}


# -- the readers --------------------------------------------------------------


@pytest.fixture()
def recorded(monkeypatch):
    """The program's summary of the cell's traced run on the chip (PR 36),
    cut to what the readers take."""
    from bagua_tpu.observability import trace_analysis

    with open(os.path.join(manifest.HERE, "testdata", CELL + ".summary.json")) as f:
        summary = json.load(f)
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", summary)
    return {"trace": {"busy_s": 1.0}, "peaks": manifest.peaks("TPU v5 lite"), "batch_per_chip": 1}


def test_the_readers_add_up_the_parts_they_name(recorded):
    from bagua_tpu.observability import trace_analysis

    parts = trace_analysis.last_summary()["model_part_ms"]
    read = {name: manifest.layer_metric_reader(name)(recorded) for name in READERS}
    assert read["st_attention_ms_per_step"] == pytest.approx(
        parts["attn_proj"] + parts["attn_core"] + parts["attn_window_core"])
    assert read["st_moe_routing_ms_per_step"] == pytest.approx(
        parts["moe_route"] + parts["moe_dispatch"] + parts["moe_combine"])
    assert read["st_moe_experts_ms_per_step"] == pytest.approx(parts["moe_experts"])
    cell = manifest.load_cell(CELL)
    peak = recorded["peaks"]["bf16_flops_per_s"]
    for name, count, part in (
            ("st_window_attention_core_roofline_pct", "window_attention_core_flops_per_sample",
             "attn_window_core"),
            ("st_full_attention_core_roofline_pct", "attention_core_flops_per_sample", "attn_core"),
            ("st_moe_experts_roofline_pct", "moe_experts_flops_per_sample", "moe_experts")):
        assert read[name] == pytest.approx(
            100 * getattr(cell.adapter, count)(cell.sizes) / (parts[part] / 1e3) / peak)
        assert 0 < read[name] < 100, name  # a share of a peak is a share
    # the three windowed layers do less than three times the global layer's work
    assert parts["attn_window_core"] < 3 * parts["attn_core"]


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_a_trace_or_without_parts(name, recorded, monkeypatch):
    read = manifest.layer_metric_reader(name)
    assert read(recorded) > 0
    assert read({**recorded, "trace": None}) is None
    # a program whose model names no part (the parent's, BERT's, VGG's)
    from bagua_tpu.observability import trace_analysis

    plain = {k: v for k, v in trace_analysis.last_summary().items() if k != "model_part_ms"}
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", plain)
    assert read(recorded) is None
    # and one without the reducer at all
    monkeypatch.delattr(trace_analysis, "last_summary")
    assert read(recorded) is None


def test_a_share_of_the_peak_needs_the_peak(recorded):
    for name in READERS:
        if name.endswith("_roofline_pct"):
            assert manifest.layer_metric_reader(name)({**recorded, "peaks": None}) is None


def test_the_recorded_parts_cover_the_forward_and_backward_pass(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    assert set(parts) == {"attn_proj", "attn_core", "attn_window_core", "moe_route", "moe_dispatch",
                          "moe_experts", "moe_combine", "head", "other"}
    both = summary["partition_ms"]["forward"] + summary["partition_ms"]["backward"]
    assert sum(parts.values()) == pytest.approx(both, rel=1e-9)
    assert parts["other"] < 0.1 * both
    assert summary["partition_ms"]["unattributed"] < 0.1 * summary["step_busy_ms"]


def test_the_six_entries_follow_lfm2s_six_and_list_this_cell_alone():
    names = [m["name"] for m in BENCH["per_layer"]]
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
        assert (entry["better"] == "higher") == name.endswith("_roofline_pct")
    # appended: together, in the issue's order, right after LFM2's six, which keep their
    # order and each its own cell alone (found by name, so a later PR may append after these)
    at = names.index(READERS[0])
    assert names[at:at + 6] == READERS and names[at - 6:at] == LFM2_READERS
    assert all(entries[name]["workloads"] == [LFM2_CELL] for name in LFM2_READERS)
    assert {entries[name]["layer"] for name in READERS} == {"attention", "expert layer"}
    # the cell and its configuration follow LFM2's, wherever a later PR's land
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells[cells.index(CELL) - 1] == LFM2_CELL
    assert configs[configs.index(CONFIG) - 1] == "lfm2-8b-a1b"
    entry = BENCH["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "dp1-b1-s8192", 1)
    assert len(entry["why"]) <= 200


def test_the_cell_reports_every_metric_without_a_list_and_its_own_six():
    cell = manifest.load_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert reported == unlisted | set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    for other in (LFM2_CELL, "glm-4.7-flash.dp1-s8192"):
        assert not {m["name"] for m in manifest.load_cell(other).per_layer} & set(READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s = 8192
    assert sz["seq_len"] == s and sz["experts_held"] == (0, 8) and sz["routed_experts_total"] == 64
    assert sz["sliding_window_layout"] == sz["rope_layout"] == (0, 1, 1, 1)
    # multiply-adds a token = the parameters of the products
    attn = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128       # q, o and k, v: 20.97 M
    expert = 3 * 2560 * 768                               # 5.898 M
    assert (attn, expert) == (20_971_520, 5_898_240)
    # the pairs a mask leaves open: the triangle with its diagonal, and inside the window
    causal = s * (s + 1) // 2
    window = sum(min(i + 1, 4096) for i in range(s))
    assert (causal, window) == (33_558_528, 25_167_872)
    assert adapter.attended_pairs(s) == causal and adapter.attended_pairs(s, 4096) == window
    assert adapter.attended_pairs(s, s) == adapter.attended_pairs(s, 2 * s) == causal
    assert adapter.attended_pairs(64, 24) == sum(min(i + 1, 24) for i in range(64))
    routed_rows = s * 6 * 8 // 64                         # 6,144 expected rows on the 8 held experts
    assert routed_rows == 6144
    forward = (4 * (s * attn + s * 2560 * 64 + routed_rows * expert)
               + 28 * (128 + 128) * (causal + 3 * window) + s * 2560 * 18992)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(12.1e12, rel=0.01)
    # the issue's own words: 3 x 2 x 2 x 28 x 128 x pairs, one global layer and three windowed
    assert adapter.attention_core_flops_per_sample(sz) == 3 * 2 * 2 * 28 * 128 * causal
    assert adapter.window_attention_core_flops_per_sample(sz) == 3 * (3 * 2 * 2 * 28 * 128 * window)
    # 6,144 rows x 3 products x 2 x 2560 x 768, x 3, in each of four layers
    assert adapter.moe_experts_flops_per_sample(sz) == 4 * 3 * (6144 * 3 * 2 * 2560 * 768)
    # the shares are of what mfu_pct counts: the parts sum to no more than the step
    assert (adapter.attention_core_flops_per_sample(sz)
            + adapter.window_attention_core_flops_per_sample(sz)
            + adapter.moe_experts_flops_per_sample(sz)) < adapter.train_flops_per_sample(sz)
    # the parameters this share holds: 370.5 M
    layer = attn + 2560 * 64 + 8 * expert + 2 * 2560
    params = 2 * 18992 * 2560 + 2560 + 4 * layer
    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 370_547_200


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = manifest.load_json(*entry["file"].split("/"))
    published = {  # the catalog's row of config.json, key for key
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": PUBLISHED_LAYOUT, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": PUBLISHED_LAYOUT,
        "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
    }
    reduced = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size",
               "sliding_window_layout", "rope_layout"]
    assert entry["reduced"] == reduced == config["reduced"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced)
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 8, 18992)
    # the kept layers are published layers 0 to 3, one whole period, in both lists
    assert config["sliding_window_layout"] == config["rope_layout"] == PUBLISHED_LAYOUT[:4] == [
        0, 1, 1, 1]
    # the floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["deployment"]["chips_sharing_each_layer"] == 8
    assert config["deployment"]["share_held"] == 0 and "370.5 M" in config["deployment"]["how"]
    assert config["moe_num_primary_experts"] * config["deployment"]["chips_sharing_each_layer"] == 64
    assert config["optimizer"]["name"] == "sgd" and config["optimizer"]["learning_rate"] == 0.01
    assert len(config["departures"]) >= 2 and set(config["assumed"]) >= {
        "router_input", "window", "secondary_experts", "optimizer", "weights", "data"}
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps every mechanism: a global layer without positions and a windowed one with
    # them, a window shorter than the toy's 64 positions, seven query heads a key-value head,
    # 2 held of 8, top-3, a slice of the vocabulary
    toy = config["toy"]
    assert toy["sliding_window_layout"] == toy["rope_layout"] == [0, 1]
    assert toy["sliding_window_size"] < manifest.load_json(
        "benchmark", "traffic", "dp1-b1-s8192.json")["toy"]["input"]["seq_len"]
    assert toy["num_attention_heads"] == 7 * toy["num_key_value_heads"]
    assert (toy["moe_num_primary_experts"], toy["published"]["moe_num_primary_experts"],
            toy["moe_num_active_primary_experts"]) == (2, 8, 3)
    assert toy["vocab_size"] < toy["published"]["vocab_size"]


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
