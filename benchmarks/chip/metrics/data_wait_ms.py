"""Host milliseconds per step that the window's loop blocks in
``Prefetcher.__next__`` (the benchmark's own ``bench/data_wait`` span)."""


def read(ctx):
    return ctx["data_wait_s"] / ctx["steps"] * 1e3
