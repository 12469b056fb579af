"""Device milliseconds per traced step under the program's ``attention``
scope (``models/attention.attention_fwd``: projections, RoPE, the flash
scan; forward, remat and backward), the union per chip, mean over chips
(``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "attention_ms")
