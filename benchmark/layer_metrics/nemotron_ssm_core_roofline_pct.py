"""Share of its roofline at which the mixers' core ran: the larger of the
time the scan's three chunk products need at the chip's bf16 peak (the
adapter's ``ssm_core_flops_per_sample``) and the time the bytes no
implementation avoids need at the memory's peak (``ssm_core_bytes_per_sample``:
``x``, ``B``, ``C``, ``dt``, ``z`` read and ``y`` written once forward, and
their cotangents once backward), over the time under
``bagua_model/part=ssm_core``.  Memory bounds it at this share's shapes."""

from benchmark import manifest
from benchmark.model_parts import part_ms

CELL = "nemotron-3-super.dp1-s8192"


def read(context):
    ms = part_ms(context, "ssm_core")
    if not ms or not context["peaks"]:
        return None
    cell = manifest.load_cell(CELL)
    flops = cell.adapter.ssm_core_flops_per_sample(cell.sizes) * context["batch_per_chip"]
    moved = cell.adapter.ssm_core_bytes_per_sample(cell.sizes) * context["batch_per_chip"]
    bound_s = max(flops / context["peaks"]["bf16_flops_per_s"],
                  moved / context["peaks"]["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3)
