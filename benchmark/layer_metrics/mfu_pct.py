"""Model FLOP/s utilization of the compiled step: the operations one
sample's forward and backward passes need (the configuration adapter's
``train_flops_per_sample``), times the samples a chip finishes per second at
the window's median step interval, over the chip's published bf16 peak.  The
median, because a traced run's window holds the capture's two stalls."""


def read(context):
    window = context["window"]
    if "step_ms_median" not in window or not context["peaks"]:
        return None
    samples_per_s_per_chip = context["batch_per_chip"] / (window["step_ms_median"] / 1e3)
    return 100.0 * context["train_flops_per_sample"] * samples_per_s_per_chip / (
        context["peaks"]["bf16_flops_per_s"])
