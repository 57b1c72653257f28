"""The ``nemotron-3-super`` configuration and its cell: the stated precision
against the control at the toy limits, runs whose timed path is broken
underneath (the state kept, half the batch, every chunk of the scan from a zero
state, the routed term and ``W_lat_out`` left out, a choice fewer a token, 8-bit
weights), the seven readers on the summary of a traced run on the chip, the
adapter's operation counts worked out on paper, and what the configuration's
file states of the cut."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest
from test_benchmark_correct import drive, toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "nemotron-3-super.dp1-s8192"
CONFIG = "nemotron-3-super"
BENCH = manifest.benchmark_json()
READERS = ["nemotron_ssm_ms_per_step", "nemotron_ssm_core_roofline_pct",
           "nemotron_moe_routing_ms_per_step", "nemotron_moe_experts_ms_per_step",
           "nemotron_moe_experts_roofline_pct", "nemotron_moe_dense_ms_per_step",
           "nemotron_attention_core_roofline_pct"]
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json"


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


def every_chunk_from_a_zero_state(monkeypatch):
    """The chunked scan with the state *not* carried: each chunk is a sequence
    of its own.  Inside a chunk it is the program's own arithmetic."""
    from bagua_tpu.models import nemotron_h

    real = nemotron_h.ssd_scan

    def chunks_apart(x, dt, a, b, c, chunk=128):
        batch, t = x.shape[:2]
        chunk = min(chunk, t)

        def fold(v):
            return v.reshape((batch * (t // chunk), chunk) + v.shape[2:])

        return real(fold(x), fold(dt), a, fold(b), fold(c), chunk).reshape(x.shape)

    monkeypatch.setattr(nemotron_h, "ssd_scan", chunks_apart)


def the_routed_term_left_out(monkeypatch):
    """The expert layer without what ``W_lat_out`` brings back: the shared
    expert alone, the latent path's weights without a gradient."""
    from bagua_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "dropless_experts",
                        lambda lowered, *args, **kwargs: jnp.zeros_like(lowered))


def a_choice_fewer_a_token(monkeypatch):
    """21 of 512 where the model takes 22 (4 of 16 for the toy's 5): the
    weights are normalised over the fewer, and the last choice's term is gone."""
    from bagua_tpu.models import nemotron_h

    validate = nemotron_h.NemotronHConfig.__post_init__

    def one_fewer(self):
        validate(self)
        object.__setattr__(self, "num_experts_per_tok", self.num_experts_per_tok - 1)

    monkeypatch.setattr(nemotron_h.NemotronHConfig, "__post_init__", one_fewer)


BROKEN = {"chunks_from_zero": every_chunk_from_a_zero_state,
          "no_latent_out": the_routed_term_left_out, "a_choice_fewer": a_choice_fewer_a_token}


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
        assert "PR 45" in limits["_readings"]
        numbers = {k: v for k, v in limits.items() if k != "_readings"}
        assert set(numbers) == {"loss_gap", "grad_rel_err", "head_rel_err", "grad_norm_gap",
                                "update_norm_gap"}
        for name, record in numbers.items():
            assert record["limit"] >= 3 * record["sound_max"] * 0.99, (group, name)  # three digits kept
        assert any(r["control_fails_it"] for r in numbers.values()), group
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
    """The program's summary of the cell's traced run on the chip (PR 45),
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
    assert read["nemotron_ssm_ms_per_step"] == pytest.approx(
        parts["ssm_proj"] + parts["ssm_conv"] + parts["ssm_core"])
    assert read["nemotron_moe_routing_ms_per_step"] == pytest.approx(
        parts["moe_route"] + parts["moe_dispatch"] + parts["moe_combine"])
    assert read["nemotron_moe_experts_ms_per_step"] == pytest.approx(parts["moe_experts"])
    assert read["nemotron_moe_dense_ms_per_step"] == pytest.approx(
        parts["moe_latent"] + parts["moe_shared"])
    cell = manifest.load_cell(CELL)
    peaks = recorded["peaks"]
    for name, count, ms in (
            ("nemotron_moe_experts_roofline_pct", "moe_experts_flops_per_sample", parts["moe_experts"]),
            ("nemotron_attention_core_roofline_pct", "attention_core_flops_per_sample",
             parts["attn_core"])):
        assert read[name] == pytest.approx(
            100 * getattr(cell.adapter, count)(cell.sizes) / (ms / 1e3) / peaks["bf16_flops_per_s"])
    # the scan's share is of the larger of its two bounds: at this share's shapes the bytes'
    by_flops = cell.adapter.ssm_core_flops_per_sample(cell.sizes) / peaks["bf16_flops_per_s"]
    by_bytes = cell.adapter.ssm_core_bytes_per_sample(cell.sizes) / peaks["hbm_bytes_per_s"]
    assert by_bytes > by_flops
    assert read["nemotron_ssm_core_roofline_pct"] == pytest.approx(
        100 * by_bytes / (parts["ssm_core"] / 1e3))
    for name in READERS:
        if name.endswith("_roofline_pct"):
            assert 0 < read[name] < 100, name  # a share of a peak is a share


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_a_trace_or_without_what_it_reads(name, recorded, monkeypatch):
    read = manifest.layer_metric_reader(name)
    assert read(recorded) > 0
    assert read({**recorded, "trace": None}) is None
    # a program whose model names no part (the parent's)
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
    assert set(parts) == {"embed", "ssm_proj", "ssm_conv", "ssm_core", "attn_proj", "attn_core",
                          "moe_route", "moe_latent", "moe_dispatch", "moe_experts", "moe_combine",
                          "moe_shared", "head", "other"}
    classes = summary["partition_ms"]
    own = classes["forward"] + classes["backward"] + classes.get("recompute", 0.0)
    assert sum(parts.values()) == pytest.approx(own, rel=1e-9)
    assert parts["other"] < 0.1 * own
    assert classes["unattributed"] < 0.1 * summary["step_busy_ms"]


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_the_seven_entries_and_the_cell_list_this_cell_alone_and_keep_their_order(bench):
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
        assert (entry["better"] == "higher") == name.endswith("_roofline_pct")
    # in the issue's order among themselves, after Ouro's five: found by name, so a later PR
    # may append after these
    assert [name for name in names if name in READERS] == READERS
    assert names.index("ouro_head_roofline_pct") < names.index(READERS[0])
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == READERS
    assert [entries[name]["layer"] for name in READERS] == [
        "state-space mixer", "state-space mixer", "expert layer", "expert layer", "expert layer",
        "expert layer", "attention"]
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("ouro-2.6b.dp1-s8192") < cells.index(CELL)
    assert configs.index("ouro-2.6b") < configs.index(CONFIG)
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "dp1-b1-s8192", 1)
    assert len(entry["why"]) <= 200
    # one four-chip cell, as before: this cell's share runs without its exchange
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["bert-large.dp4"]


def test_the_cell_reports_every_metric_without_a_list_and_its_own_seven():
    cell = manifest.load_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert reported == unlisted | set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s_per_chip", "step_ms_p95", "setup_s"}
    for other in ("ouro-2.6b.dp1-s8192", "glm-4.7-flash.dp1-s8192", "bert-large.dp1"):
        assert not {m["name"] for m in manifest.load_cell(other).per_layer} & set(READERS)


# -- the counts and the cut ---------------------------------------------------


def test_operation_counts_at_the_published_sizes_worked_out_on_paper():
    cell = manifest.load_cell(CELL)
    sz, adapter = cell.sizes, cell.adapter
    s, h = 8192, 4096
    assert sz["seq_len"] == s and sz["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert (sz["experts_held"], sz["mamba_heads_held"], sz["attention_heads_held"]) == (
        (0, 8), (0, 16), (0, 4))
    assert (sz["routed_experts_total"], sz["mamba_heads_total"], sz["n_groups_total"],
            sz["attention_heads_total"], sz["key_value_heads_total"]) == (512, 128, 8, 32, 2)
    # the rows the held experts expect: 8,192 x 22 x 8 / 512, 352 an expert
    assert adapter.expected_routed_rows(sz) == 2816
    # multiply-adds of a forward pass, by part
    mixer_proj = s * (h * (1024 + 1280 + 16) + 1024 * h)
    scan = s * 129 / 2 * (128 + 16 * 64) + 2 * s * 16 * 64 * 128
    causal = s * (s + 1) // 2
    assert causal == 33_558_528
    attn = s * h * 128 * (2 * 4 + 2 * 1) + 4 * 2 * 128 * causal
    experts = (s * h * 512 + 2 * s * h * 1024 + 2816 * 2 * 1024 * 2688 + 2 * s * h * 5376)
    head = s * h * 16384
    forward = 5 * (mixer_proj + scan) + attn + 5 * experts + head
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(21.2e12, rel=0.01)
    # the issue's own words, by class (TFLOP): shared experts 10.8, latent projections 2.1, head
    # 3.3, experts 0.47 at the expected rows, the scans' chunk products under 0.2
    assert 6 * 5 * 2 * s * h * 5376 == pytest.approx(10.8e12, rel=0.005)
    assert 6 * 5 * 2 * s * h * 1024 == pytest.approx(2.06e12, rel=0.005)
    assert 6 * head == pytest.approx(3.3e12, rel=0.005)
    assert adapter.moe_experts_flops_per_sample(sz) == 3 * 2 * 2816 * 2 * 1024 * 2688 * 5
    assert adapter.moe_experts_flops_per_sample(sz) == pytest.approx(0.465e12, rel=0.005)
    assert adapter.ssm_core_flops_per_sample(sz) == 6 * 5 * scan < 0.2e12
    assert adapter.attention_core_flops_per_sample(sz) == 3 * 2 * 2 * 4 * 128 * causal
    # the bytes no implementation of the scan avoids: x, B, C, z, y at two bytes, dt at four,
    # forward, and as much again backward, five mixers
    assert adapter.ssm_core_bytes_per_sample(sz) == 2 * 5 * s * (2 * (1280 + 1024 + 1024) + 4 * 16)
    # the shared expert, whole here, is half of the step
    assert 6 * 5 * 2 * s * h * 5376 / adapter.train_flops_per_sample(sz) == pytest.approx(0.51, abs=0.01)
    # the parameters, to the parameter
    mixer = h * 2320 + 1024 * h + 4 * 1280 + 1280 + 3 * 16 + 1024 + h
    attention = h * 512 + 2 * h * 128 + 512 * h + h
    layer = h * 512 + 512 + 2 * h * 1024 + 2 * 8 * 1024 * 2688 + 2 * h * 5376 + h
    params = 5 * mixer + attention + 5 * layer + 2 * 16384 * h + h
    assert (mixer, attention, layer) == (13_708_592, 5_246_976, 98_570_752)
    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 700_865_520
    assert cell.config["parameters"] == params
    # embedding and head are 19% of them
    assert 2 * 16384 * h / params == pytest.approx(0.19, abs=0.005)


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = manifest.load_json(*entry["file"].split("/"))
    published = {  # the catalog's row of config.json, key for key
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096,
        "hybrid_override_pattern": ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                                    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
    }
    reduced = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
               "mamba_num_heads", "n_groups", "num_attention_heads", "num_key_value_heads",
               "vocab_size", "num_nextn_predict_layers"]
    assert entry["reduced"] == reduced == config["reduced"]
    assert entry["source"] == config["source"] == SOURCE
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced) | {"mtp_hybrid_override_pattern"}
    # no width is cut: none of these is in ``reduced``
    widths = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size", "expand", "conv_kernel",
              "chunk_size", "intermediate_size", "moe_intermediate_size", "moe_latent_size",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok")
    assert not set(widths) & set(reduced)
    # published blocks 26 to 36, the first whole period of eleven
    period = published["hybrid_override_pattern"][26:37]
    assert config["hybrid_override_pattern"] == period == "EMEMEMEMEM*"
    assert published["hybrid_override_pattern"].index("EMEMEMEMEM*") == 26
    assert config["num_hidden_layers"] == 11 and config["num_nextn_predict_layers"] == 0
    # the deployment: 8 chips share the mixers and the vocabulary, 64 the experts
    deployment = config["deployment"]
    assert deployment["chips"] == 64 and deployment["chips_sharing_each_mixer"] == 8
    assert deployment["chips_sharing_the_experts"] == 64
    assert deployment["share_held"] == deployment["mixer_share_held"] == 0
    assert config["n_routed_experts"] * deployment["chips_sharing_the_experts"] == 512
    for key, total in (("mamba_num_heads", 128), ("n_groups", 8), ("num_attention_heads", 32),
                       ("vocab_size", 131072)):
        assert config[key] * deployment["chips_sharing_each_mixer"] == total, key
    assert config["num_key_value_heads"] == 1  # of 2: four chips read each
    assert "700,865,520" in deployment["how"] and config["parameters"] == 700_865_520
    # at the floors: a whole period, 8 experts, an eighth of the vocabulary
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= 131072
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert len(config["departures"]) >= 3 and "shared expert" in config["departures"][0]
    assert set(config["assumed"]) >= {
        "attention_positions", "router_input", "shared_expert", "gate_then_norm", "dt",
        "router_eps", "optimizer", "weights", "data"}
    assert all(isinstance(v, str) and len(v) > 40 for v in config["assumed"].values())
    assert "352" in config["what_the_cut_distorts"]
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps every mechanism: the four kinds of block, more choices than experts held,
    # whole groups, a sequence of several chunks
    toy = config["toy"]
    assert set(toy["hybrid_override_pattern"]) == set("ME*-")
    assert toy["num_experts_per_tok"] > toy["n_routed_experts"]
    assert toy["published"]["mamba_num_heads"] // toy["published"]["n_groups"] == (
        toy["mamba_num_heads"] // toy["n_groups"])
    traffic = manifest.load_json("benchmark", "traffic", "dp1-b1-s8192.json")
    assert traffic["toy"]["input"]["seq_len"] > toy["chunk_size"]


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
