"""Milliseconds per captured step that device 0 spent in the attention of
BERT's 24 encoder layers, forward and backward: the fused query-key-value
product with its bias, the three heads taken apart and their gradients joined,
and the output product (``bagua_model/part=attn_proj``), and the core, scores,
softmax and the probabilities' product at 16 heads of 64 over 128 positions
(``attn_core``), from the program's summary of the capture.  Nothing where the
program's model names no part (before PR 51)."""

from benchmark.model_parts import part_ms


def read(context):
    return part_ms(context, "attn_proj", "attn_core")
