"""Device milliseconds per step, mean over the cell's chips, of the trace's
collective ops (``trace_reduce.COLLECTIVE``) in the traced segment; nothing
when the trace holds none."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["collective_ops"]:
        return None
    return t["collective_s"] / ctx["trace_steps"] * 1e3
