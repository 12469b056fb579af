"""Device milliseconds per traced step under the program's ``optimizer``
scope (``optim/adamw.adamw_update``: global norm, clip, moments, the
update), the union per chip, mean over chips (``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "optimizer_ms")
