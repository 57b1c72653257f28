"""The ``glm-4.7-flash`` configuration and its cell: the stated precision
against the control at the toy limits, the five readers of the model's parts,
the adapter's operation counts worked out on paper, and what the
configuration's file states of the cut."""

import json
import os

import pytest

from benchmark import check, manifest
from test_benchmark_correct import toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "glm-4.7-flash.dp1-s8192"
BENCH = manifest.benchmark_json()
READERS = {
    "attention_ms_per_step": 164.33714433333222,      # attn_proj + attn_core
    "moe_dispatch_ms_per_step": 36.914231166665814,     # moe_route + moe_dispatch + moe_combine
    "moe_experts_ms_per_step": 22.905248166664368,
    "attention_core_roofline_pct": 45.26315727264066,
    "moe_experts_roofline_pct": 20.559492244842588,
}


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
    # the number that separates the precisions: the whole gradient also
    # carries flips of the top-k choice between bfloat16 and float32 states
    limit = cell.tolerances["head_rel_err"]["limit"]
    assert control["head_rel_err"] > limit > sound["head_rel_err"]


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 29" in limits["_readings"]
        numbers = {k: v for k, v in limits.items() if k != "_readings"}
        assert set(numbers) == {"loss_gap", "grad_rel_err", "head_rel_err", "grad_norm_gap",
                                "update_norm_gap"}
        for name, record in numbers.items():
            assert record["limit"] >= 3 * record["sound_max"] * 0.99, (group, name)  # three digits kept
        assert any(r["control_fails_it"] for r in numbers.values()), group
        assert numbers["head_rel_err"]["control_fails_it"], group


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
    """The program's summary of the cell's traced run on the chip (PR 29),
    cut to what the readers take."""
    from bagua_tpu.observability import trace_analysis

    with open(os.path.join(manifest.HERE, "testdata", CELL + ".summary.json")) as f:
        summary = json.load(f)
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", summary)
    return {"trace": {"busy_s": 1.0}, "peaks": manifest.peaks("TPU v5 lite"), "batch_per_chip": 1}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_its_number_on_the_recorded_summary(name, recorded):
    assert manifest.layer_metric_reader(name)(recorded) == pytest.approx(READERS[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_without_a_trace_or_without_parts(name, recorded, monkeypatch):
    read = manifest.layer_metric_reader(name)
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
    for name in ("attention_core_roofline_pct", "moe_experts_roofline_pct"):
        assert manifest.layer_metric_reader(name)({**recorded, "peaks": None}) is None


def test_the_recorded_parts_cover_the_forward_and_backward_pass(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    assert set(parts) == {"attn_proj", "attn_core", "moe_route", "moe_dispatch", "moe_experts",
                          "moe_combine", "moe_shared", "dense_mlp", "head", "other"}
    both = summary["partition_ms"]["forward"] + summary["partition_ms"]["backward"]
    assert sum(parts.values()) == pytest.approx(both, rel=1e-9)
    assert parts["other"] < 0.1 * both


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_every_new_metric_lists_the_cell_and_moves_its_rate(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
    # the five keep the issue's order among themselves, found by name: where they stand in
    # the list is the driver's to say, and it appends every later entry
    assert [m["name"] for m in bench["per_layer"] if m["name"] in READERS] == list(READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s = 8192
    assert sz["seq_len"] == s and sz["experts_held"] == (0, 8) and sz["routed_experts_total"] == 64
    # multiply-adds of one layer's latent projections = its 21.76 M parameters a token
    proj = 2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64) + 512 * 20 * (192 + 256) + 20 * 256 * 2048
    assert proj == 21_757_952
    core = 20 * (256 + 256) * s * s // 2            # scores and mixing, the causal half
    dense = 3 * 2048 * 10240
    shared = 3 * 2048 * 1536
    routed_rows = s * 4 * 8 // 64                   # 4,096 expected rows on the 8 held experts
    assert routed_rows == 4096
    forward = (5 * (s * proj + core) + s * dense
               + 4 * (s * 2048 * 64 + s * shared + routed_rows * shared) + s * 2048 * 19360)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(23.5e12, rel=0.005)
    # the issue's own words: 3 x 2 x 2 x 20 x 256 x 8192^2 / 2 a layer
    assert adapter.attention_core_flops_per_sample(sz) == 5 * 3 * 2 * 2 * 20 * 256 * s * s / 2
    # 4,096 rows x 3 products x 2 x 2048 x 1536, x 3, in each of four layers
    assert adapter.moe_experts_flops_per_sample(sz) == 4 * 3 * (4096 * 3 * 2 * 2048 * 1536)
    # with the prediction module: its projection, one more expert layer, the head again
    extra = adapter.train_flops_per_sample({**sz, "num_nextn_predict_layers": 1}) / 6.0 - forward
    assert extra == pytest.approx(
        s * 2 * 2048 * 2048 + s * proj + core + s * 2048 * 64 + s * shared
        + routed_rows * shared + s * 2048 * 19360, rel=1e-12)


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash")
    config = manifest.load_json(*entry["file"].split("/"))
    published = {  # the catalog's row of config.json, number for number
        "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
        "moe_intermediate_size": 1536, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 1000000, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
    }
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert entry["reduced"] == reduced == config["reduced"]
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (5, 8, 19360, 0)
    # the floors: a whole period and four layers after the dense one, 8 experts, an eighth
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["deployment"]["chips_sharing_each_layer"] == 8
    assert config["model_type"] == "glm4_moe_lite" and config["topk_method"] == "noaux_tc"
    assert config["norm_topk_prob"] is True and config["rope_scaling"] is None
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert len(config["departures"]) >= 3 and set(config["assumed"]) >= {
        "optimizer", "mtp_loss_weight", "weights", "data"}
    # the toy keeps every mechanism: 2 held of 8, top-2, a dense and two expert layers, both parts
    toy = config["toy"]
    assert (toy["n_routed_experts"], toy["published"]["n_routed_experts"],
            toy["num_experts_per_tok"], toy["num_hidden_layers"]) == (2, 8, 2, 3)
    assert toy["qk_nope_head_dim"] != toy["qk_rope_head_dim"] > 0


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
