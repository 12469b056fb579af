"""The SSD's share of its roofline: the least time the chip could take for
every chunked scan of a traced step, the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth (``flops/<name>.ssd_step_work``,
from the shapes of the cell's configuration alone; ``peaks.json``), over
the device time under the program's ``ssd`` scope (``ssm_scopes.py``),
per chip, in percent."""

from benchmarks.chip import harness, ssm_scopes


def read(ctx):
    ssd_ms = ssm_scopes.reading(ctx, "ssd_ms")
    if not ssd_ms:
        return None
    cell = ssm_scopes.cell_of("ssd_roofline_pct")
    flops, bytes_ = harness.load_module("flops", cell.config["flops"]).ssd_step_work(
        cell.config["program"], cell.traffic)
    peak = ctx["peaks"][ctx["device_kind"]]
    least_s = max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"]) / ctx["chips"]
    return least_s / (ssd_ms * 1e-3) * 100.0
