"""Collective ops per traced step per chip under the program's
``grad_sync`` scope, a start and its done counted once: the
latency-bound launches of the chunk schedule (``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "sync_launches")
