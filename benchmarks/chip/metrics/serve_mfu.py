"""serve_mfu: the window's serving operations (each admitted prompt's
prefill, each decoded token over its live context; the architecture's
``prefill_flops`` and ``decode_flops``, ``archs/<a>.py``) over its wall
time, as a share of the chip's bf16 peak."""


def read(run):
    if run.kind != "serve":
        return None
    return 100.0 * run.flops / run.window_s / run.peak["flops_bf16"]
