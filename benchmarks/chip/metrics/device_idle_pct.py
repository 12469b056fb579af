"""Share of the traced segment in which no op ran on the chip, mean over
the cell's chips: 1 - busy union / window."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
