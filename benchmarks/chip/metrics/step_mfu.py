"""Model FLOPs utilization of the whole step: the configuration's model
FLOPs per step (``flops/<name>.py``, no remat recomputation) times the
steps of the window, over the window's host-clock seconds, over chips
times the chip's bf16 peak from ``peaks.json`` (an unknown chip is an
error)."""


def read(ctx):
    peak = ctx["peaks"][ctx["device_kind"]]["bf16_flops_per_s"]
    flops = ctx["model_flops_per_step"] * ctx["steps"]
    return flops / ctx["window_s"] / (ctx["chips"] * peak) * 100.0
