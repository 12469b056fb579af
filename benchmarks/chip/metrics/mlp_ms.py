"""Device milliseconds per traced step under the program's ``mlp`` scope
(``models/transformer._ffn_part``: its norm, the FFN or MoE, the residual
add), the union per chip, mean over chips (``scopes.py``)."""

from benchmarks.chip import scopes


def read(ctx):
    return scopes.reading(ctx, "mlp_ms")
