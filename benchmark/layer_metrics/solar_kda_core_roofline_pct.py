"""Share of its roofline at which the KDA mixers' core ran: the larger of the
time the delta rule's chunk products need at the chip's bf16 peak (the
adapter's ``kda_core_flops_per_sample``: the chunked form at the
configuration's stated chunk of 64, whatever implements it) and the time the
bytes no implementation avoids need at the memory's peak
(``kda_core_bytes_per_sample``: ``q``, ``k``, ``v``, ``g``, ``beta`` read and
``o`` written once forward, and their cotangents once backward), over the time
under ``bagua_model/part=kda_core``.  Memory bounds it at this share's
shapes."""

from benchmark import manifest
from benchmark.model_parts import part_ms

CELL = "solar-open2-250b.dp1-s8192"


def read(context):
    ms = part_ms(context, "kda_core")
    if not ms or not context["peaks"]:
        return None
    cell = manifest.load_cell(CELL)
    flops = cell.adapter.kda_core_flops_per_sample(cell.sizes) * context["batch_per_chip"]
    moved = cell.adapter.kda_core_bytes_per_sample(cell.sizes) * context["batch_per_chip"]
    bound_s = max(flops / context["peaks"]["bf16_flops_per_s"],
                  moved / context["peaks"]["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3)
