"""What the per-layer readers of a model's parts take from the program's
summary of the traced run's capture: ``model_part_ms``, milliseconds per
captured step under each ``bagua_model/part=...`` scope, forward and backward
together.  A program without such scopes, or a run without a device trace,
has none: every reader then gives None and its metric is left out."""

from benchmark import manifest
from benchmark.step_summary import summary


def part_ms(context, *parts):
    """The sum of the named parts' milliseconds per step, or None where the
    summary has no part at all."""
    found = summary(context)
    by_part = found.get("model_part_ms") if found else None
    if not by_part:
        return None
    return sum(by_part.get(part, 0.0) for part in parts)


def roofline_pct(context, metric: str, count: str, *parts):
    """The operations the adapter's ``count`` function gives for one step of
    the metric's cell, over the parts' time, over the chip's bf16 peak."""
    ms = part_ms(context, *parts)
    if not ms or not context["peaks"]:
        return None
    entry = next(m for m in manifest.benchmark_json()["per_layer"] if m["name"] == metric)
    if len(entry["workloads"]) != 1:
        raise ValueError(f"{metric} counts one cell's shapes, and lists {entry['workloads']}")
    cell = manifest.load_cell(entry["workloads"][0])
    flops = getattr(cell.adapter, count)(cell.sizes) * context["batch_per_chip"]
    return 100.0 * flops / (ms / 1e3) / context["peaks"]["bf16_flops_per_s"]
