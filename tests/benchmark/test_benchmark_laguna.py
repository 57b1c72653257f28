"""The ``laguna-xs.2`` configuration and its cell: the stated precision against
the control at the toy limits, runs whose timed path is broken underneath (the
window left out, the gate left out, plain rotary on the global layers, the
routed scaling left out, 8-bit weights), the six readers on the summary of a
traced run on the chip, the adapter's operation counts worked out on paper, and
what the configuration's file states of the cut."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest
from test_benchmark_correct import drive, toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "laguna-xs.2.dp1-s8192"
CONFIG = "laguna-xs.2"
BENCH = manifest.benchmark_json()
READERS = ["laguna_attention_ms_per_step", "laguna_window_attention_core_roofline_pct",
           "laguna_full_attention_core_roofline_pct", "laguna_moe_routing_ms_per_step",
           "laguna_moe_experts_ms_per_step", "laguna_moe_experts_roofline_pct"]
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
FULL, SLIDING = "full_attention", "sliding_attention"


# -- correct ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [2_400_000_011, 2**31 + 5])
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


def test_the_adapters_round_trip_and_the_program_with_8_bit_weights_does_not_pass():
    def patch(adapter):
        build = adapter.build_loss
        adapter.build_loss = lambda sizes: check.lower_precision(build(sizes))

    cell, run = toy_run(CELL, 2_400_000_011, patch_adapter=patch)
    passed, lines = check.verdict(run.numbers(run.reference()), cell.tolerances)
    assert not passed, lines
    # ``to_program`` is a rearrangement: every leaf of the reference's tree once, nothing cast
    sz = cell.sizes
    ref = cell.reference.init_params(jax.random.PRNGKey(0), sz)
    mapped = cell.adapter.to_program(cell.adapter.as_stored(ref), sz)
    assert sorted(map(id, jax.tree.leaves(mapped))) == sorted(map(id, jax.tree.leaves(ref)))
    assert mapped["layer_1"]["attn"]["gate_proj"] is ref["layers"][1]["w_g"]
    assert mapped["layer_1"]["moe"]["correction_bias"] is ref["layers"][1]["b_router"]
    assert mapped["layer_0"]["mlp"]["down"] is ref["layers"][0]["w_down"]


def the_window_left_out(monkeypatch):
    """Every layer attending to all earlier keys: the mask is part of the
    arithmetic ``correct`` holds the program to."""
    from bagua_tpu.models import laguna

    real = laguna.causal_attention
    monkeypatch.setattr(laguna, "causal_attention",
                        lambda q, k, v, scale, window=None: real(q, k, v, scale))


def _config_with(monkeypatch, **changed):
    """``LagunaConfig`` with fields set after its own validation: the model the
    adapter builds then differs from the configuration's file in them alone."""
    from bagua_tpu.models import laguna

    validate = laguna.LagunaConfig.__post_init__

    def altered(self):
        validate(self)
        for name, value in changed.items():
            object.__setattr__(self, name, value(self) if callable(value) else value)

    monkeypatch.setattr(laguna.LagunaConfig, "__post_init__", altered)


def the_gate_left_out(monkeypatch):
    """The attention's result goes to ``W_o`` as the core gave it; ``W_g``
    stays in the tree and takes no gradient."""
    _config_with(monkeypatch, gating=False)


def plain_rotary_on_the_global_layers(monkeypatch):
    """The global layers turn all columns at the windowed layers' plain
    frequencies: no YaRN blend, no factor, no pass-through half."""
    _config_with(monkeypatch, rope_parameters=lambda cfg: tuple(
        (kind, dict(cfg.rope_parameters)[SLIDING]) for kind, _ in cfg.rope_parameters))


def the_routed_scaling_left_out(monkeypatch):
    """The chosen experts' weights sum to 1 where the model has them sum to
    ``moe_routed_scaling_factor``."""
    _config_with(monkeypatch, moe_routed_scaling_factor=1.0)


BROKEN = {"no_window": the_window_left_out, "no_gate": the_gate_left_out,
          "plain_rotary": plain_rotary_on_the_global_layers,
          "no_routed_scaling": the_routed_scaling_left_out}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_program_broken_in_a_new_mechanism_is_not_correct(fault, monkeypatch, capsys):
    BROKEN[fault](monkeypatch)
    result = drive(capsys, CELL)
    assert result["correct"] is False, result["checks"]
    limits = manifest.load_cell(CELL, dry=True).tolerances
    over = {name: value / limits[name]["limit"] for name, value in result["checks"].items()}
    assert max(over.values()) > 1.5, over  # no near miss
    assert result["failed"] == 0  # the step runs and its losses are finite: the check finds it


def test_an_unbroken_run_in_this_process_is_correct(capsys):
    assert drive(capsys, CELL)["correct"] is True


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 49" in limits["_readings"]
        numbers = {k: v for k, v in limits.items() if k != "_readings"}
        assert set(numbers) == {"loss_gap", "grad_rel_err", "head_rel_err", "grad_norm_gap",
                                "update_norm_gap"}
        for name, record in numbers.items():
            assert record["limit"] >= 3 * record["sound_max"] * 0.99, (group, name)  # three digits kept
        assert any(r["control_fails_it"] for r in numbers.values()), group
    assert ("TPU v5 lite x1" in detail["tolerances"]["_readings"]
            and "cpu x1" in detail["toy_tolerances"]["_readings"])


# -- the readers --------------------------------------------------------------


@pytest.fixture()
def recorded(monkeypatch):
    """The program's summary of the cell's traced run on the chip (PR 49),
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
    assert read["laguna_attention_ms_per_step"] == pytest.approx(
        parts["attn_proj"] + parts["attn_gate"] + parts["attn_core"] + parts["attn_window_core"])
    assert read["laguna_moe_routing_ms_per_step"] == pytest.approx(
        parts["moe_route"] + parts["moe_dispatch"] + parts["moe_combine"])
    assert read["laguna_moe_experts_ms_per_step"] == pytest.approx(parts["moe_experts"])
    cell = manifest.load_cell(CELL)
    peak = recorded["peaks"]["bf16_flops_per_s"]
    for name, count, part in (
            ("laguna_window_attention_core_roofline_pct", "window_attention_core_flops_per_sample",
             "attn_window_core"),
            ("laguna_full_attention_core_roofline_pct", "attention_core_flops_per_sample",
             "attn_core"),
            ("laguna_moe_experts_roofline_pct", "moe_experts_flops_per_sample", "moe_experts")):
        assert read[name] == pytest.approx(
            100 * getattr(cell.adapter, count)(cell.sizes) / (parts[part] / 1e3) / peak)
        assert 0 < read[name] < 100, name  # a share of a peak is a share
    # the three windowed layers' cores take less than the two global ones'
    assert parts["attn_window_core"] < parts["attn_core"]


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_a_trace_or_without_what_it_reads(name, recorded, monkeypatch):
    read = manifest.layer_metric_reader(name)
    assert read(recorded) > 0
    assert read({**recorded, "trace": None}) is None
    if name.endswith("_roofline_pct"):  # a share of the peak needs the peak
        assert read({**recorded, "peaks": None}) is None
    # a program whose model names no part (the parent's)
    from bagua_tpu.observability import trace_analysis

    plain = {k: v for k, v in trace_analysis.last_summary().items() if k != "model_part_ms"}
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", plain)
    assert read(recorded) is None
    # and one without the reducer at all
    monkeypatch.delattr(trace_analysis, "last_summary")
    assert read(recorded) is None


def test_the_recorded_parts_cover_the_forward_and_backward_pass(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    assert set(parts) == {"embed", "attn_proj", "attn_gate", "attn_core", "attn_window_core",
                          "dense_mlp", "moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                          "moe_shared", "head", "other"}
    classes = summary["partition_ms"]
    own = classes["forward"] + classes["backward"] + classes.get("recompute", 0.0)
    assert sum(parts.values()) == pytest.approx(own, rel=1e-9)
    assert parts["other"] < 0.1 * own
    assert classes["unattributed"] < 0.1 * summary["step_busy_ms"]


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_the_six_entries_and_the_cell_list_this_cell_alone_and_keep_their_order(bench):
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
        assert (entry["better"] == "higher") == name.endswith("_roofline_pct")
    # in the issue's order among themselves, after Nemotron's seven: found by name, so a later
    # PR may append after these
    assert [name for name in names if name in READERS] == READERS
    assert names.index("nemotron_attention_core_roofline_pct") < names.index(READERS[0])
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == READERS
    assert [entries[name]["layer"] for name in READERS] == [
        "attention", "attention", "attention", "expert layer", "expert layer", "expert layer"]
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("nemotron-3-super.dp1-s8192") < cells.index(CELL)
    assert configs.index("nemotron-3-super") < configs.index(CONFIG)
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "dp1-b1-s8192", 1)
    assert len(entry["why"]) <= 200
    # one four-chip cell, as before: this cell's share runs without its exchange
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["bert-large.dp4"]


def test_the_cell_reports_every_metric_without_a_list_and_its_own_six():
    cell = manifest.load_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert reported == unlisted | set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    for other in ("smallthinker-21ba3b.dp1-s8192", "glm-4.7-flash.dp1-s8192", "bert-large.dp1"):
        assert not {m["name"] for m in manifest.load_cell(other).per_layer} & set(READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s, h = 8192, 2048
    assert sz["seq_len"] == s and sz["experts_held"] == (0, 32) and sz["routed_experts_total"] == 256
    assert sz["layer_types"] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert sz["num_attention_heads_per_layer"] == (48, 64, 64, 64, 48)
    assert sz["mlp_layer_types"] == ("dense", "sparse", "sparse", "sparse", "sparse")
    # the pairs a mask leaves open: the triangle with its diagonal, and inside the window
    causal = s * (s + 1) // 2
    window = sum(min(i + 1, 512) for i in range(s))
    assert (causal, window) == (33_558_528, 4_063_488)
    assert adapter.attended_pairs(s) == causal and adapter.attended_pairs(s, 512) == window
    assert adapter.attended_pairs(64, 24) == sum(min(i + 1, 24) for i in range(64))
    # tiles of 1,024 would cover 8 diagonal and 7 sub-diagonal tiles: 3.9 times the window
    assert 15 * 1024 * 1024 / window == pytest.approx(3.87, abs=0.01)
    # the rows the 32 held experts expect: 8,192 x 8 x 32 / 256, 256 an expert
    assert adapter.expected_routed_rows(sz) == 8192
    # multiply-adds a token = the parameters of the products, by kind of layer
    full_attn = 2 * h * 48 * 128 + 2 * h * 8 * 128 + h * 48
    window_attn = 2 * h * 64 * 128 + 2 * h * 8 * 128 + h * 64
    assert (full_attn, window_attn) == (29_458_432, 37_879_808)
    dense, expert, shared, router = 3 * h * 8192, 3 * h * 512, 3 * h * 512, h * 256
    forward = (s * (2 * full_attn + 3 * window_attn + dense + 4 * (router + shared))
               + 4 * 8192 * expert + 2 * 48 * 256 * causal + 3 * 64 * 256 * window
               + s * h * 12544)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(19.7e12, rel=0.005)
    # the issue's shares of the step: projections 43%, global cores 25%, windowed cores 6%,
    # the dense MLP 13%, the head 6%, shared and routed experts 3% each
    parts = adapter.part_counts(sz)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    for part, want in (("attn_proj", 0.43), ("attn_core", 0.25), ("attn_window_core", 0.06),
                       ("dense_mlp", 0.13), ("head", 0.06), ("moe_shared", 0.03),
                       ("moe_experts", 0.03)):
        assert share[part] == pytest.approx(want, abs=0.006), part
    assert share["attn_proj"] + share["attn_core"] + share["attn_window_core"] > 0.73
    # the issue's own words: 3 x 2 x 2 x heads x 128 x pairs
    assert adapter.attention_core_flops_per_sample(sz) == 2 * (3 * 2 * 2 * 48 * 128 * causal)
    assert adapter.window_attention_core_flops_per_sample(sz) == 3 * (3 * 2 * 2 * 64 * 128 * window)
    assert adapter.moe_experts_flops_per_sample(sz) == 4 * 3 * (8192 * 3 * 2 * h * 512)
    # the parameters this share holds, to the parameter
    layer0 = full_attn + dense + 2 * h
    moe = router + 256 + shared + 32 * expert
    assert moe == 104_333_568
    params = (layer0 + 3 * (window_attn + moe + 2 * h) + full_attn + moe + 2 * h
              + 2 * 12544 * h + h)
    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 691_624_960
    assert cell.config["parameters"] == params


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = manifest.load_json(*entry["file"].split("/"))
    layer_types = [FULL, SLIDING, SLIDING, SLIDING] * 10
    published = {  # the catalog's row of config.json, key for key
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": layer_types, "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    }
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert entry["reduced"] == reduced == config["reduced"]
    assert entry["source"] == config["source"] == SOURCE
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced)
    # no width is cut: none of these is in ``reduced``
    widths = ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_experts_per_tok", "num_key_value_heads",
              "sliding_window", "rope_parameters", "partial_rotary_factor")
    assert not set(widths) & set(reduced)
    # published layers 0 to 4: the leading dense layer, then one whole period
    assert config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert config[key] == published[key][:5], key
    assert config["layer_types"][1:] == [SLIDING, SLIDING, SLIDING, FULL]
    # at the floors in depth and vocabulary, four times the floor in experts
    assert config["vocab_size"] * 8 == published["vocab_size"] and config["num_experts"] == 4 * 8
    deployment = config["deployment"]
    assert deployment["chips_sharing_each_layer"] == 8 and deployment["share_held"] == 0
    assert "8 chips share each layer" in deployment["how"] and "691,624,960" in deployment["how"]
    assert config["num_experts"] * deployment["chips_sharing_each_layer"] == 256
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert len(config["departures"]) >= 2 and "224 experts" in config["departures"][1]
    assert set(config["assumed"]) >= {
        "router_scores", "gate_nonlinearity", "head_norm", "router_eps", "router_softcap",
        "yarn_truncate", "window", "optimizer", "weights", "data"}
    assert all(isinstance(v, str) and len(v) > 40 for v in config["assumed"].values())
    assert "256 expected rows" in config["what_the_cut_distorts"]
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps every mechanism: both layer types, both head counts, a window shorter than
    # the toy sequence, more experts than are held, YaRN on half of a head's columns
    toy = config["toy"]
    assert set(toy["layer_types"]) == {FULL, SLIDING} and toy["mlp_layer_types"][0] == "dense"
    assert len(set(toy["num_attention_heads_per_layer"])) == 2
    traffic = manifest.load_json("benchmark", "traffic", "dp1-b1-s8192.json")
    assert toy["sliding_window"] < traffic["toy"]["input"]["seq_len"]
    assert toy["num_experts"] < toy["published"]["num_experts"]
    assert toy["rope_parameters"][FULL]["rope_type"] == "yarn"
    assert toy["rope_parameters"][FULL]["partial_rotary_factor"] == 0.5


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
