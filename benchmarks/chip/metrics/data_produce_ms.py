"""Mean host milliseconds of the data pipeline's ``data/produce`` spans
(``data/pipeline.Prefetcher``: one batch made and placed on the devices)
that end inside the traced window: the producer's headroom against
``step_ms`` (``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "data_produce_ms")
