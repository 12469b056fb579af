"""Device milliseconds per traced step under the program's ``grad_sync``
scope (``core/collectives.grad_sync``: every op of the gradient sync,
permutes and the chunk updates alike), the union per chip, mean over chips
(``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "sync_ms")
