"""train_mfu: the window's training operations (the architecture's
``train_flops_per_token`` x tokens, ``archs/<a>.py``: forward and backward
matmuls and causal attention, no recomputation) over its wall time, as a
share of the chip's bf16 peak."""


def read(run):
    if run.kind != "train":
        return None
    return 100.0 * run.flops / run.window_s / run.peak["flops_bf16"]
