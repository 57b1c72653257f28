"""The ``lfm2-8b-a1b`` configuration and its cell: the stated precision
against the control at the toy limits, the six readers of the model's parts,
the adapter's operation counts worked out on paper, and what the
configuration's file states of the cut."""

import json
import os

import pytest

from benchmark import check, manifest
from test_benchmark_correct import toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "lfm2-8b-a1b.dp1-s8192"
GLM_CELL = "glm-4.7-flash.dp1-s8192"
BENCH = manifest.benchmark_json()
READERS = {
    "conv_mixer_ms_per_step": 23.69585483333327,            # conv_proj + conv_core
    "gqa_attention_ms_per_step": 20.371187499999955,         # attn_proj + attn_core
    "gqa_attention_core_roofline_pct": 27.050975436882904,
    "lfm2_moe_routing_ms_per_step": 37.723136333333,      # moe_route + moe_dispatch + moe_combine
    "lfm2_moe_experts_ms_per_step": 26.964899166664708,
    "lfm2_moe_experts_roofline_pct": 40.74978961805694,
}
GLM_READERS = ["attention_ms_per_step", "moe_dispatch_ms_per_step", "moe_experts_ms_per_step",
               "attention_core_roofline_pct", "moe_experts_roofline_pct"]


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
    # the numbers that separate the precisions: the embedding's gradient, which
    # is the head's, and the whole gradient
    for name in ("head_rel_err", "grad_rel_err"):
        limit = cell.tolerances[name]["limit"]
        assert control[name] > limit > sound[name], name


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 33" in limits["_readings"]
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
    """The program's summary of the cell's traced run on the chip (PR 33),
    cut to what the readers take."""
    from bagua_tpu.observability import trace_analysis

    with open(os.path.join(manifest.HERE, "testdata", CELL + ".summary.json")) as f:
        summary = json.load(f)
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", summary)
    return {"trace": {"busy_s": 1.0}, "peaks": manifest.peaks("TPU v5 lite"), "batch_per_chip": 1}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_its_number_on_the_recorded_summary(name, recorded):
    assert manifest.layer_metric_reader(name)(recorded) == pytest.approx(READERS[name], rel=1e-9)


def test_the_readers_add_up_the_parts_they_name(recorded):
    from bagua_tpu.observability import trace_analysis

    parts = trace_analysis.last_summary()["model_part_ms"]
    read = {name: manifest.layer_metric_reader(name)(recorded) for name in READERS}
    assert read["conv_mixer_ms_per_step"] == pytest.approx(parts["conv_proj"] + parts["conv_core"])
    assert read["gqa_attention_ms_per_step"] == pytest.approx(parts["attn_proj"] + parts["attn_core"])
    assert read["lfm2_moe_routing_ms_per_step"] == pytest.approx(
        parts["moe_route"] + parts["moe_dispatch"] + parts["moe_combine"])
    assert read["lfm2_moe_experts_ms_per_step"] == pytest.approx(parts["moe_experts"])
    cell = manifest.load_cell(CELL)
    peak = recorded["peaks"]["bf16_flops_per_s"]
    assert read["gqa_attention_core_roofline_pct"] == pytest.approx(
        100 * cell.adapter.attention_core_flops_per_sample(cell.sizes) / (parts["attn_core"] / 1e3) / peak)
    assert read["lfm2_moe_experts_roofline_pct"] == pytest.approx(
        100 * cell.adapter.moe_experts_flops_per_sample(cell.sizes) / (parts["moe_experts"] / 1e3) / peak)
    # a share of a peak is a share
    assert 0 < read["gqa_attention_core_roofline_pct"] < 100
    assert 0 < read["lfm2_moe_experts_roofline_pct"] < 100


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
    for name in ("gqa_attention_core_roofline_pct", "lfm2_moe_experts_roofline_pct"):
        assert manifest.layer_metric_reader(name)({**recorded, "peaks": None}) is None


def test_the_recorded_parts_cover_the_forward_and_backward_pass(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    assert set(parts) == {"conv_proj", "conv_core", "attn_proj", "attn_core", "moe_route",
                          "moe_dispatch", "moe_experts", "moe_combine", "dense_mlp", "head", "other"}
    both = summary["partition_ms"]["forward"] + summary["partition_ms"]["backward"]
    assert sum(parts.values()) == pytest.approx(both, rel=1e-9)
    assert parts["other"] < 0.1 * both
    assert summary["partition_ms"]["unattributed"] < 0.08 * summary["step_busy_ms"]


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_the_six_entries_follow_glms_five_and_list_this_cell_alone(bench):
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
    # the six keep their order among themselves and come after GLM's five, which keep theirs
    # and each its own cell alone: all found by name, so a later PR may append after them
    assert [name for name in names if name in READERS] == list(READERS)
    assert [name for name in names if name in GLM_READERS] == GLM_READERS
    assert max(map(names.index, GLM_READERS)) < min(map(names.index, READERS))
    assert all(entries[name]["workloads"] == [GLM_CELL] for name in GLM_READERS)
    layers = {m["layer"] for m in bench["per_layer"]}
    assert {entries[name]["layer"] for name in READERS} == {
        "short convolution", "attention", "expert layer"} <= layers
    # the cell and its configuration stand once, after GLM's, and say what they said
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.count(CELL) == 1 and cells.index(GLM_CELL) < cells.index(CELL)
    assert configs.count("lfm2-8b-a1b") == 1
    assert configs.index("glm-4.7-flash") < configs.index("lfm2-8b-a1b")
    cell = bench["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-8b-a1b", "dp1-b1-s8192", 1)


def test_the_cell_reports_every_metric_without_a_list_and_its_own_six():
    cell = manifest.load_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert reported == unlisted | set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    glm = {m["name"] for m in manifest.load_cell(GLM_CELL).per_layer}
    assert not glm & set(READERS) and not reported & set(GLM_READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s = 8192
    assert sz["seq_len"] == s and sz["experts_held"] == (0, 8) and sz["routed_experts_total"] == 32
    assert sz["layer_types"] == ("conv", "full_attention", "conv", "conv", "conv")
    # multiply-adds a token = the parameters of the products
    conv = 2048 * 3 * 2048 + 2048 * 2048                 # in and out: 16.78 M
    attn = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64        # q, o and k, v: 10.49 M
    assert (conv, attn) == (16_777_216, 10_485_760)
    core = 32 * (64 + 64) * s * s // 2                   # scores and mixing, the causal half
    dense = 3 * 2048 * 7168                              # 44.04 M
    expert = 3 * 2048 * 1792                             # 11.01 M
    routed_rows = s * 4 * 8 // 32                        # 8,192 expected rows on the 8 held experts
    assert (dense, expert, routed_rows) == (44_040_192, 11_010_048, 8192)
    forward = (4 * s * conv + s * attn + core + s * dense
               + 4 * (s * 2048 * 32 + routed_rows * expert) + s * 2048 * 16384)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(10.63e12, rel=0.005)
    # the issue's own words: 3 x 2 x 2 x 32 x 64 x 8192^2 / 2 in the one attention layer
    assert adapter.attention_core_flops_per_sample(sz) == 3 * 2 * 2 * 32 * 64 * s * s / 2
    # 8,192 rows x 3 products x 2 x 2048 x 1792, x 3, in each of four layers
    assert adapter.moe_experts_flops_per_sample(sz) == 4 * 3 * (8192 * 3 * 2 * 2048 * 1792)
    # the parameters this share holds: 507.8 M
    params = (16384 * 2048 + 2048 + (conv + 3 * 2048 + dense + 2 * 2048)
              + (attn + 2 * 64 + 2 * 2048 + 2048 * 32 + 32 + 8 * expert)
              + 3 * (conv + 3 * 2048 + 2 * 2048 + 2048 * 32 + 32 + 8 * expert))
    import jax

    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 507_820_288


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b")
    config = manifest.load_json(*entry["file"].split("/"))
    layer_types = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
                   "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv",
                   "full_attention", "conv", "conv"]
    published = {  # the catalog's row of config.json, key for key
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": layer_types, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536,
    }
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
    assert entry["reduced"] == reduced == config["reduced"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced)
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 1, 8, 16384)
    # the kept layers are published layers 1 to 5: a dense conv layer and a whole period
    assert config["layer_types"] == layer_types[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    # the floors: a whole period and four layers after the dense one, 8 experts, an eighth or more
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4
    assert config["vocab_size"] * 4 == published["vocab_size"]
    assert config["deployment"]["chips_sharing_each_layer"] == 4
    assert config["deployment"]["share_held"] == 0 and "507.8 M" in config["deployment"]["how"]
    assert config["num_experts"] * config["deployment"]["chips_sharing_each_layer"] == 32
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert config["router_eps"] == 1e-6
    assert len(config["departures"]) >= 2 and set(config["assumed"]) >= {
        "tie_word_embeddings", "router_eps", "optimizer", "weights", "data"}
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps every mechanism: a conv dense layer, an attention and a conv expert layer,
    # two query heads a key-value head, 2 held of 8, top-2, a slice of the vocabulary
    toy = config["toy"]
    assert toy["layer_types"] == ["conv", "full_attention", "conv"] and toy["num_dense_layers"] == 1
    assert toy["num_attention_heads"] == 2 * toy["num_key_value_heads"]
    assert (toy["num_experts"], toy["published"]["num_experts"], toy["num_experts_per_tok"]) == (2, 8, 2)
    assert toy["vocab_size"] < toy["published"]["vocab_size"]


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
