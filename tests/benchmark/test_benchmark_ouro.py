"""The ``ouro-2.6b`` configuration and its cell: the stated precision against
the control at the toy limits, runs whose timed path is broken underneath (the
state kept, half the batch, three passes of four, the entropy term left out,
the next pass fed the un-normed state, 8-bit weights), the five readers on the
summary of a traced run on the chip, the adapter's operation counts worked out
on paper, and what the configuration's file states of the cut."""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from benchmark import check, manifest
from test_benchmark_correct import drive, toy_run
from test_benchmark_run import later_pr, run_cell

CELL = "ouro-2.6b.dp1-s8192"
CONFIG = "ouro-2.6b"
BENCH = manifest.benchmark_json()
READERS = ["ouro_layer_products_ms_per_step", "ouro_layer_products_roofline_pct",
           "ouro_attention_core_roofline_pct", "ouro_exits_ms_per_step", "ouro_head_roofline_pct"]
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


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


def test_a_program_of_three_passes_is_not_correct(monkeypatch, capsys):
    """The timed path with the last pass dropped: the third takes what mass is
    left, every weight is visited three times, and three exits are read."""
    from bagua_tpu.models import ouro

    validate = ouro.OuroConfig.__post_init__

    def one_pass_fewer(self):
        validate(self)
        object.__setattr__(self, "total_ut_steps", self.total_ut_steps - 1)

    monkeypatch.setattr(ouro.OuroConfig, "__post_init__", one_pass_fewer)
    result = drive(capsys, CELL)
    assert result["correct"] is False
    limits = manifest.load_cell(CELL, dry=True).tolerances
    assert result["checks"]["grad_rel_err"] > 3 * limits["grad_rel_err"]["limit"]
    assert result["checks"]["loss_gap"] > limits["loss_gap"]["limit"]


def test_a_loss_without_the_entropy_term_is_not_correct(monkeypatch, capsys):
    from bagua_tpu.models import ouro

    monkeypatch.setattr(ouro, "distribution_entropy", lambda p: jnp.zeros(p.shape[1:], p.dtype))
    result = drive(capsys, CELL)
    assert result["correct"] is False
    # beta x H(p), 0.1 x about 1.3 nats a position: the loss says so by itself
    limits = manifest.load_cell(CELL, dry=True).tolerances
    assert result["checks"]["loss_gap"] > 50 * limits["loss_gap"]["limit"]
    assert 0.08 < result["checks"]["loss_gap"] < 0.1 * 1.3863


def test_a_next_pass_fed_the_un_normed_state_is_not_correct():
    """The hand-over between passes is part of the arithmetic: a program whose
    exits read the normed state and whose next pass reads the stream as the
    layers left it does not pass."""
    from bagua_tpu.models import ouro

    class UnNormedHandOver(ouro.OuroModel):
        @nn.compact
        def __call__(self, ids, targets):
            cfg, dt = self.cfg, self.cfg.compute_dtype
            x = ouro.embed(self.kernel("embedding", cfg.vocab_size, cfg.hidden_size), ids, dt)
            layers = [ouro.OuroBlock(cfg, name=f"layer_{n}") for n in range(cfg.num_hidden_layers)]
            final_norm = ouro.RMSNorm(cfg.rms_norm_eps, name="final_norm")
            head = self.kernel("lm_head", cfg.hidden_size, cfg.vocab_size)
            w_exit = self.kernel("exit_gate", cfg.hidden_size)
            b_exit = self.param("exit_gate_bias", nn.initializers.zeros, (), jnp.float32)
            exits, gates = [], []
            for _ in range(cfg.total_ut_steps):
                for layer in layers:
                    x = layer(x)
                h, entropies = ouro._exit(final_norm(x), head, targets)  # x goes on as it is
                exits.append(entropies)
                gates.append(h.astype(jnp.float32) @ w_exit + b_exit)
            return jnp.stack(exits), jnp.stack(gates)

    def patch(adapter):
        adapter.build_loss = lambda sizes: ouro.ouro_loss_fn(
            UnNormedHandOver(adapter.model_config(sizes)))

    cell, run = toy_run(CELL, 2_400_000_011, patch_adapter=patch)
    numbers = run.numbers(run.reference())
    passed, lines = check.verdict(numbers, cell.tolerances)
    assert not passed, lines
    assert numbers["grad_rel_err"] > 3 * cell.tolerances["grad_rel_err"]["limit"]


def test_an_unbroken_run_in_this_process_is_correct(capsys):
    assert drive(capsys, CELL)["correct"] is True


def test_the_cells_limits_are_on_record_and_the_control_fails_one():
    detail = manifest.load_json("benchmark", "workloads", CELL + ".json")
    for group in ("tolerances", "toy_tolerances"):
        limits = detail[group]
        assert "PR 42" in limits["_readings"]
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
    """The program's summary of the cell's traced run on the chip (PR 42),
    cut to what the readers take."""
    from bagua_tpu.observability import trace_analysis

    with open(os.path.join(manifest.HERE, "testdata", CELL + ".summary.json")) as f:
        summary = json.load(f)
    monkeypatch.setattr(trace_analysis, "_LAST_SUMMARY", summary)
    return {"trace": {"busy_s": 1.0}, "peaks": manifest.peaks("TPU v5 lite"), "batch_per_chip": 1}


def test_the_readers_add_up_the_parts_they_name(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts = summary["model_part_ms"]
    read = {name: manifest.layer_metric_reader(name)(recorded) for name in READERS}
    assert read["ouro_layer_products_ms_per_step"] == pytest.approx(
        parts["attn_proj"] + parts["dense_mlp"])
    assert read["ouro_exits_ms_per_step"] == pytest.approx(parts["head"] + parts["exit_gate"])
    cell = manifest.load_cell(CELL)
    peak = recorded["peaks"]["bf16_flops_per_s"]
    for name, count, ms in (
            ("ouro_layer_products_roofline_pct", "layer_products_flops_per_sample",
             parts["attn_proj"] + parts["dense_mlp"]),
            ("ouro_attention_core_roofline_pct", "attention_core_flops_per_sample",
             parts["attn_core"]),
            ("ouro_head_roofline_pct", "head_flops_per_sample", parts["head"])):
        assert read[name] == pytest.approx(
            100 * getattr(cell.adapter, count)(cell.sizes) / (ms / 1e3) / peak)
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


def test_the_recorded_parts_and_passes_cover_the_three_classes(recorded):
    from bagua_tpu.observability import trace_analysis

    summary = trace_analysis.last_summary()
    parts, passes = summary["model_part_ms"], summary["model_pass_ms"]
    assert set(parts) == {"embed", "attn_proj", "attn_core", "dense_mlp", "head", "exit_gate",
                          "other"}
    classes = summary["partition_ms"]
    own = classes["forward"] + classes["backward"] + classes["recompute"]
    assert sum(parts.values()) == pytest.approx(own, rel=1e-9)
    assert set(passes) == {"1", "2", "3", "4"} and summary["layer_applications_per_step"] == 16
    # what runs under no pass: the lookup, its gradient and the loss that joins the exits
    assert 0 < own - sum(passes.values()) < 0.01 * own
    # four passes of the same layers take the same time to within a tenth
    assert max(passes.values()) < 1.1 * min(passes.values())
    assert parts["other"] < 0.1 * own
    assert classes["unattributed"] < 0.1 * summary["step_busy_ms"]


@pytest.mark.parametrize("bench", [BENCH, later_pr(BENCH)], ids=["as_it_stands", "after_a_later_pr"])
def test_the_five_entries_and_the_cell_list_this_cell_alone_and_keep_their_order(bench):
    names = [m["name"] for m in bench["per_layer"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "samples_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert (entry["unit"] == "%") == name.endswith("_roofline_pct")
        assert (entry["better"] == "higher") == name.endswith("_roofline_pct")
    # in the issue's order among themselves, after the seven of set-up: found by name, so a
    # later PR may append after these
    assert [name for name in names if name in READERS] == READERS
    assert names.index("setup_named_pct") < names.index(READERS[0])
    # every entry that lists this cell alone is one of the five: none reads a class the step
    # no longer has
    assert [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] == READERS
    assert [entries[name]["layer"] for name in READERS] == [
        "looped stack", "looped stack", "attention", "exits", "exits"]
    cells = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert cells.index("smallthinker-21ba3b.dp1-s8192") < cells.index(CELL)
    assert configs.index("smallthinker-21ba3b") < configs.index(CONFIG)
    entry = bench["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "dp1-b1-s8192", 1)
    assert len(entry["why"]) <= 200
    # one four-chip cell, as before
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["bert-large.dp4"]


def test_the_cell_reports_every_metric_without_a_list_and_its_own_five():
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
    s = 8192
    assert sz["seq_len"] == s and sz["total_ut_steps"] == 4 and sz["num_hidden_layers"] == 4
    assert adapter.layer_applications(sz) == 16
    # multiply-adds a token = the parameters of the products
    attn, mlp = 4 * 2048 * 16 * 128, 3 * 2048 * 5632
    assert (attn, mlp) == (16_777_216, 34_603_008)
    causal = s * (s + 1) // 2
    assert causal == 33_558_528
    core = 16 * (128 + 128) * causal                      # scores and mixing, every open pair
    head = s * 2048 * 49152
    # the issue's own words
    assert adapter.layer_products_flops_per_sample(sz) == 3 * 2 * s * (4 * 2048**2 + mlp) * 16
    assert adapter.layer_products_flops_per_sample(sz) == pytest.approx(40.4e12, rel=0.005)
    assert adapter.attention_core_flops_per_sample(sz) == 16 * (3 * 2 * 2 * 16 * 128 * causal)
    assert adapter.attention_core_flops_per_sample(sz) == pytest.approx(16 * 0.825e12, rel=0.005)
    assert adapter.head_flops_per_sample(sz) == 3 * 2 * s * 2048 * 49152 * 4
    assert adapter.head_flops_per_sample(sz) == pytest.approx(19.8e12, rel=0.005)
    forward = 16 * (s * (attn + mlp) + core) + 4 * head
    assert adapter.train_flops_per_sample(sz) == pytest.approx(6.0 * forward, rel=1e-12)
    assert adapter.train_flops_per_sample(sz) == pytest.approx(73.4e12, rel=0.005)
    # the three shares are of what mfu_pct counts, each application and each exit once: they
    # sum to the step, so none can pass 100% whatever the memory plan runs again
    assert (adapter.layer_products_flops_per_sample(sz) + adapter.attention_core_flops_per_sample(sz)
            + adapter.head_flops_per_sample(sz)) == adapter.train_flops_per_sample(sz)
    # the exits are 27% of the step here
    assert adapter.head_flops_per_sample(sz) / adapter.train_flops_per_sample(sz) == pytest.approx(
        0.27, abs=0.005)
    # the parameters: 406.88 M, a layer 51,388,416
    layer = attn + mlp + 4 * 2048
    params = 4 * layer + 2 * 49152 * 2048 + 2048 + 2049
    shapes = jax.eval_shape(lambda k: cell.reference.init_params(k, sz), jax.random.PRNGKey(0))
    assert layer == 51_388_416
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params == 406_884_353
    # embedding and head are 49% of them
    assert 2 * 49152 * 2048 / params == pytest.approx(0.49, abs=0.005)


def test_the_file_states_every_published_width_and_the_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    config = manifest.load_json(*entry["file"].split("/"))
    published = {  # the catalog's row of config.json, key for key
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    }
    reduced = ["num_hidden_layers", "layer_types"]
    assert entry["reduced"] == reduced == config["reduced"]
    assert entry["source"] == config["source"] == SOURCE
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value and config[key] != value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced)
    assert config["num_hidden_layers"] == 4 and config["layer_types"] == ["full_attention"] * 4
    # nothing shared out: every layer and the vocabulary whole on the chip, 4 of 48 layers
    assert config["deployment"]["chips_sharing_each_layer"] == 1
    assert config["deployment"]["layers_held"] == "4 of 48" and "406.88 M" in config["deployment"]["how"]
    assert config["optimizer"] == {"name": "sgd", "learning_rate": 0.01}
    assert len(config["departures"]) >= 2 and set(config["assumed"]) >= {
        "four_norms", "norm_between_passes", "exit_gate", "objective", "entropy_beta",
        "which_line_differs", "optimizer", "weights", "data"}
    assert config["assumed"]["entropy_beta"] == 0.1
    assert all(isinstance(v, str) and len(v) > 40
               for k, v in config["assumed"].items() if k != "entropy_beta")
    assert set(config["precision"]) == {"compute", "stored", "control"}
    # the toy keeps the mechanism: two layers run four times, as many key-value heads as heads
    toy = config["toy"]
    assert toy["num_hidden_layers"] == 2 and toy["layer_types"] == ["full_attention"] * 2
    assert toy["num_attention_heads"] == toy["num_key_value_heads"] and "total_ut_steps" not in toy


def test_the_parent_has_no_such_cell_and_says_so_at_once():
    proc = run_cell("--workload", CELL + "-absent", "--seed", "1", "--seconds", "1",
                    "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no workload" in proc.stderr and "BENCHMARK.json" in proc.stderr
