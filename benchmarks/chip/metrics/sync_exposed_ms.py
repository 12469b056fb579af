"""The part of ``sync_ms`` in which no other op runs on that chip's
``XLA Ops`` line: sync time that no compute hides, per traced step, mean
over chips (``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "sync_exposed_ms")
