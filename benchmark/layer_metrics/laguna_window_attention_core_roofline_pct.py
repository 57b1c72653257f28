"""Share of the chip's bf16 peak at which the windowed attention cores ran:
the operations of scores and mixing over the pairs *inside* the window (at
8,192 positions and 512 keys 4,063,488 a layer, whatever tiles the kernel
covers them with), 64 query heads, forward and backward, nothing recomputed
(the adapter's ``window_attention_core_flops_per_sample``), over the time
under ``bagua_model/part=attn_window_core``.  Compute bounds it."""

from benchmark.model_parts import roofline_pct


def read(context):
    return roofline_pct(context, "laguna_window_attention_core_roofline_pct",
                        "window_attention_core_flops_per_sample", "attn_window_core")
