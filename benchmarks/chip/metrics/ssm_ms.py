"""Device milliseconds per traced step under the program's ``ssm`` scope
(``models/ssm._mamba2_mix``: in_proj, conv, SSD, gated norm, out_proj of
every Mamba-2 mixer; forward, remat and backward), the union per chip,
mean over chips (``ssm_scopes.py``)."""

from benchmarks.chip import ssm_scopes


def read(ctx):
    return ssm_scopes.reading(ctx, "ssm_ms")
