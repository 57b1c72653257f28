"""Finds everything that belongs to one cell by the names in
``BENCHMARK.json``: the cell's, configuration's and traffic mix's data
files, the configuration's adapter and plain reference, the per-layer metric
readers, the table of peaks.  Adding a cell, a mix, a configuration or a
per-layer metric is adding files and entries; no file here changes."""

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(relpath: str) -> ModuleType:
    """A module from a file named like a configuration (``bert-large.py``),
    which the import statement cannot name."""
    path = os.path.join(ROOT, relpath)
    name = "benchmark_file_" + "".join(c if c.isalnum() else "_" for c in relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json() -> dict:
    return load_json("BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration's JSON (toy sizes merged in when dry)
    traffic: dict       # the traffic mix's JSON (likewise)
    tolerances: dict    # the limits of ``correct``, from the cell's own JSON
    adapter: ModuleType
    reference: ModuleType
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def sizes(self) -> dict:
        return self.adapter.sizes(self.config, self.traffic["input"])

    @property
    def global_batch(self) -> int:
        return self.traffic["batch_per_chip"] * self.chips


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, dry: bool = False) -> Cell:
    bench = benchmark_json()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json: {[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    detail = load_json("benchmark", "workloads", name + ".json")
    for key in ("config", "traffic"):
        if detail[key] != entry[key]:
            raise SystemExit(f"{name}: BENCHMARK.json and workloads/{name}.json disagree on {key}")
    config = load_json("benchmark", "configs", entry["config"] + ".json")
    traffic = load_json("benchmark", "traffic", entry["traffic"] + ".json")
    if traffic["chips"] != entry["chips"]:
        raise SystemExit(f"{name}: chips {entry['chips']} but the mix is for {traffic['chips']}")
    if dry:
        config = {**config, **config["toy"]}
        traffic = {**traffic, **traffic["toy"]}
    return Cell(
        name=name, chips=entry["chips"], config=config, traffic=traffic,
        tolerances=detail["toy_tolerances" if dry else "tolerances"],
        adapter=load_module(config["adapter"]), reference=load_module(config["reference"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def layer_metric_reader(name: str):
    """``read(context) -> float or None`` of ``layer_metrics/<name>.py``."""
    return load_module(os.path.join("benchmark", "layer_metrics", name + ".py")).read


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind that is not in the table is an
    error, never a default."""
    table = load_json("benchmark", "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; "
            f"benchmark/peaks.json lists {sorted(k for k in table if k != 'source')}")
    return table[device_kind]
