"""The ``solar-open2-250b`` configuration and its cell: the stated precision
against the control at the toy limits, runs whose timed path is broken
underneath (``beta`` left in (0, 1), one decay a head in place of one a
channel, the GQA gate left out, the router's normalisation left out, 8-bit
weights), the six readers on the summary of a traced run on the chip, the
adapter's operation and byte counts worked out on paper, and what the
configuration's file states of the cut."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest
from test_benchmark_correct import drive, toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "solar-open2-250b.dp1-s8192"
CONFIG = "solar-open2-250b"
BENCH = manifest.benchmark_json()
READERS = ["solar_kda_ms_per_step", "solar_kda_core_roofline_pct",
           "solar_attention_core_roofline_pct", "solar_moe_routing_ms_per_step",
           "solar_moe_experts_ms_per_step", "solar_moe_experts_roofline_pct"]
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
REDUCED = ["num_hidden_layers", "gqa_layers", "num_attention_heads", "num_key_value_heads",
           "linear_attn_config", "linear_attn_config.num_heads", "n_routed_experts", "vocab_size"]


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
    assert mapped["layer_0"]["attn"]["gate_proj"] is ref["layers"][0]["w_g"]
    assert mapped["layer_1"]["kda"]["f_b_proj"] is ref["layers"][1]["w_f2"]
    assert mapped["layer_1"]["kda"]["A_log"] is ref["layers"][1]["a_log"]
    assert mapped["layer_2"]["moe"]["correction_bias"] is ref["layers"][2]["b_router"]


def _config_with(monkeypatch, **changed):
    """``SolarOpen2Config`` with fields set after its own validation: the model
    the adapter builds then differs from the configuration's file in them
    alone."""
    from bagua_tpu.models import solar_open2

    validate = solar_open2.SolarOpen2Config.__post_init__

    def altered(self):
        validate(self)
        for name, value in changed.items():
            object.__setattr__(self, name, value)

    monkeypatch.setattr(solar_open2.SolarOpen2Config, "__post_init__", altered)


def beta_left_in_0_1(monkeypatch):
    """``beta = sigmoid(h W_b)`` without its doubling: ``I - beta k k^T`` loses
    its negative eigenvalues."""
    _config_with(monkeypatch, kda_allow_neg_eigval=False)


def one_decay_a_head(monkeypatch):
    """The channels' mean decay in every channel of a head: the gated delta
    rule of one decay a head, in the place of KDA's one a channel."""
    from bagua_tpu.models import solar_open2

    real, core = solar_open2.gated_delta_rule, solar_open2._kda_core.__wrapped__
    monkeypatch.setattr(
        solar_open2, "gated_delta_rule", lambda q, k, v, g, beta, chunk: real(
            q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta, chunk))
    # ``jax.checkpoint`` keeps a function's trace: a new function, or a run earlier in this
    # process answers for this one
    monkeypatch.setattr(solar_open2, "_kda_core", jax.checkpoint(
        lambda *operands: core(*operands), static_argnums=(7, 8)))


def the_gqa_gate_left_out(monkeypatch):
    """The attention's result goes to ``W_o`` as the core gave it; ``W_g``
    stays in the tree and takes no gradient."""
    _config_with(monkeypatch, use_gqa_gate=False)


def the_routers_normalisation_left_out(monkeypatch):
    """The chosen experts' weights are their sigmoids, not divided by their
    sum."""
    _config_with(monkeypatch, norm_topk_prob=False)


BROKEN = {"beta_in_0_1": beta_left_in_0_1, "one_decay_a_head": one_decay_a_head,
          "no_gqa_gate": the_gqa_gate_left_out,
          "no_router_normalisation": the_routers_normalisation_left_out}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_program_broken_in_a_new_mechanism_is_not_correct(fault, monkeypatch, capsys):
    BROKEN[fault](monkeypatch)
    result = drive(capsys, CELL)
    assert result["correct"] is False, result["checks"]
    limits = manifest.load_cell(CELL, dry=True).tolerances
    over = {name: value / limits[name]["limit"] for name, value in result["checks"].items()}
    # no near miss, as far as the toy can tell: ``beta`` halved reads 1.28 times the limit of the
    # whole gradient there (its mixers write into the stream at a twentieth of the embedding's
    # size); the chip's readings of the same three faults are in PERF.md section 4
    assert max(over.values()) > (1.2 if fault == "beta_in_0_1" else 1.5), over
    assert result["failed"] == 0  # the step runs and its losses are finite: the check finds it


def test_an_unbroken_run_in_this_process_is_correct(capsys):
    assert drive(capsys, CELL)["correct"] is True


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 52" in limits["_readings"]
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
    """The program's summary of the cell's traced run on the chip (PR 52),
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
    assert read["solar_kda_ms_per_step"] == pytest.approx(
        parts["kda_proj"] + parts["kda_conv"] + parts["kda_core"] + parts["kda_gate_norm"])
    assert read["solar_moe_routing_ms_per_step"] == pytest.approx(
        parts["moe_route"] + parts["moe_dispatch"] + parts["moe_combine"])
    assert read["solar_moe_experts_ms_per_step"] == pytest.approx(parts["moe_experts"])
    cell = manifest.load_cell(CELL)
    peaks = recorded["peaks"]
    for name, count, part in (
            ("solar_attention_core_roofline_pct", "attention_core_flops_per_sample", "attn_core"),
            ("solar_moe_experts_roofline_pct", "moe_experts_flops_per_sample", "moe_experts")):
        assert read[name] == pytest.approx(
            100 * getattr(cell.adapter, count)(cell.sizes) / (parts[part] / 1e3)
            / peaks["bf16_flops_per_s"])
    # the delta rule's roofline is the memory's at this share: its bytes take longer than its products
    by_products = cell.adapter.kda_core_flops_per_sample(cell.sizes) / peaks["bf16_flops_per_s"]
    by_bytes = cell.adapter.kda_core_bytes_per_sample(cell.sizes) / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_products
    assert read["solar_kda_core_roofline_pct"] == pytest.approx(
        100 * by_bytes / (parts["kda_core"] / 1e3))
    for name in READERS:
        if name.endswith("_roofline_pct"):
            assert 0 < read[name] < 100, name  # a share of a peak is a share


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
    assert set(parts) == {"embed", "kda_proj", "kda_conv", "kda_core", "kda_gate_norm",
                          "attn_proj", "attn_gate", "attn_core", "moe_route", "moe_dispatch",
                          "moe_experts", "moe_combine", "moe_shared", "head", "other"}
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
    # in the issue's order among themselves, after the accepted entries: found by name, so a
    # later PR may append after these
    assert [name for name in names if name in READERS] == READERS
    assert names.index("bert_attention_ms_per_step") < names.index(READERS[0])
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == READERS
    assert [entries[name]["layer"] for name in READERS] == [
        "linear-attention mixer", "linear-attention mixer", "attention", "expert layer",
        "expert layer", "expert layer"]
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("laguna-xs.2.dp1-s8192") < cells.index(CELL)
    assert configs.index("laguna-xs.2") < configs.index(CONFIG)
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "dp1-b1-s8192", 1)
    assert len(entry["why"]) <= 200 and "32k" in entry["why"] and "205 rows" in entry["why"]
    # one four-chip cell, as before: this cell's share runs without its exchange
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["bert-large.dp4"]


def test_the_cell_reports_every_metric_without_a_list_and_its_own_six():
    cell = manifest.load_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert reported == unlisted | set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    for other in ("nemotron-3-super.dp1-s8192", "laguna-xs.2.dp1-s8192", "bert-large.dp1"):
        assert not {m["name"] for m in manifest.load_cell(other).per_layer} & set(READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_and_byte_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s, h, d = 8192, 4096, 128
    assert sz["seq_len"] == s and sz["experts_held"] == (0, 8) and sz["routed_experts_total"] == 320
    assert sz["heads_held"] == (0, 8) and sz["attention_heads_total"] == 64
    assert sz["key_value_heads_total"] == 8 and sz["gqa_layers"] == (0,) and sz["chunk_size"] == 64
    # the rows the 8 held experts expect: 8,192 x 8 x 8 / 320, 205 an expert, of a buffer of 65,536
    assert adapter.expected_routed_rows(sz) == pytest.approx(1638.4)
    assert adapter.expected_routed_rows(sz) / 8 == pytest.approx(204.8)
    # multiply-adds a token = the parameters of the products, by part
    gqa = 3 * h * 8 * d + 2 * h * d
    kda = 4 * h * 8 * d + 2 * (h * d + d * 8 * d) + h * 8
    assert (gqa, kda) == (13_631_488, 18_120_704)
    causal = s * (s + 1) // 2
    core = 8 * (5 * d * 65 / 2 + 3 * d * d)  # a token: 8 heads, chunks of 64, three d x d products
    assert core == 8 * 69_952
    router, unit = h * 320, 3 * h * 1280
    forward = (s * (gqa + 3 * kda + 4 * (router + unit)) + 8 * 2 * d * causal + 3 * s * core
               + 4 * 1638.4 * unit + s * h * 24576)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(12.8e12, rel=0.01)
    # the issue's shares of the step: the head 39%, the shared experts 24%, the KDA mixers'
    # products 21% and their cores 1%, the GQA mixer 8%, routed experts 5%, the router 2%
    parts = adapter.part_counts(sz)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    for part, want in (("head", 0.39), ("moe_shared", 0.24), ("kda_proj", 0.21),
                       ("kda_core", 0.01), ("moe_experts", 0.05), ("moe_route", 0.02)):
        assert share[part] == pytest.approx(want, abs=0.007), (part, share[part])
    assert share["attn_proj"] + share["attn_gate"] + share["attn_core"] == pytest.approx(0.08, abs=0.007)
    # the roofline's counts, functions of the sizes alone
    assert adapter.kda_core_flops_per_sample(sz) == 3 * (3 * 2 * s * core)
    assert adapter.kda_core_bytes_per_sample(sz) == 3 * 2 * s * 8 * (d * (3 * 2 + 4 + 2) + 4)
    assert adapter.attention_core_flops_per_sample(sz) == 3 * 2 * 2 * 8 * d * causal
    assert adapter.moe_experts_flops_per_sample(sz) == 4 * 3 * (1638.4 * 3 * 2 * h * 1280)
    # the parameters this share holds, to the parameter
    kda_params = kda + 3 * 4 * 8 * d + 8 * d + 8 + 8 * d + d
    moe = router + 320 + unit + 8 * unit
    assert (kda_params, moe) == (18_135_176, 142_868_800)
    params = gqa + 3 * kda_params + 4 * (moe + 2 * h) + 2 * 24576 * h + h
    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 840_875_672
    assert cell.config["parameters"] == params


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = manifest.load_json(*entry["file"].split("/"))
    linear = {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    published = {  # the catalog's row of config.json, key for key
        "model_type": "solar_open2", "partial_rotary_factor": 1, "linear_attn_config": linear,
        "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    }
    # the issue's seven keys, and the changed group under its top-level key too
    assert entry["reduced"] == REDUCED == config["reduced"]
    assert entry["source"] == config["source"] == SOURCE
    for key, value in published.items():
        if key in REDUCED:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(REDUCED)
    assert config["published"]["linear_attn_config.num_heads"] == 64
    # no width is cut: none of these is in ``reduced``, and the changed group keeps its own
    widths = ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "rms_norm_eps", "n_shared_experts")
    assert not set(widths) & set(REDUCED)
    assert config["linear_attn_config"] == {**linear, "num_heads": 8}
    # published layers 0 to 3: one whole period, GQA, KDA, KDA, KDA
    assert config["num_hidden_layers"] == 4 and config["gqa_layers"] == [0]
    assert published["gqa_layers"][:2] == [0, 4]
    # at the floors: four layers, 8 experts, an eighth of the vocabulary
    assert config["vocab_size"] * 8 == published["vocab_size"] and config["n_routed_experts"] == 8
    assert config["num_attention_heads"] * 8 == 64 and config["num_key_value_heads"] * 8 == 8
    deployment = config["deployment"]
    assert (deployment["chips"], deployment["chips_sharing_each_mixer"],
            deployment["chips_sharing_the_experts"]) == (40, 8, 40)
    assert deployment["how"].startswith(
        "one chip of 40: 8 chips share each layer's mixers by heads and the vocabulary, all 40 "
        "the experts")
    assert "840,875,672" in deployment["how"] and config["n_routed_experts"] * 40 == 320
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert len(config["departures"]) == 3 and "312 experts" in config["departures"][1]
    assert "shared expert" in config["departures"][2]
    assert set(config["assumed"]) >= {
        "kda_equations", "kda_low_rank", "kda_conv_and_norms", "kda_beta", "kda_chunk",
        "kda_decay_init", "gqa_gate", "gqa_head_norm", "router_scores", "router_eps",
        "intermediate_size", "optimizer", "weights", "data"}
    assert all(isinstance(v, str) and len(v) > 40 for v in config["assumed"].values())
    assert "one value a head column" in config["assumed"]["gqa_gate"]
    assert "205 expected rows" in config["what_the_cut_distorts"]
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps every mechanism: both kinds of mixer, more heads and experts than are held,
    # a sequence of several chunks
    toy = config["toy"]
    assert toy["gqa_layers"] == [0] and toy["num_hidden_layers"] == 3
    assert toy["num_attention_heads"] < toy["published"]["num_attention_heads"]
    assert (toy["linear_attn_config"]["num_heads"]
            < toy["published"]["linear_attn_config"]["num_heads"])
    assert toy["n_routed_experts"] < toy["published"]["n_routed_experts"]
    traffic = manifest.load_json("benchmark", "traffic", "dp1-b1-s8192.json")
    assert traffic["toy"]["input"]["seq_len"] >= 4 * toy["chunk_size"]


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
