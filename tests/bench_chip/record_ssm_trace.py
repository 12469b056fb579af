"""Records the small chip trace with the Mamba-2 mixer's scopes that
``test_bench_chip_granite.py`` reads
(``benchmarks/chip/testdata/small_ssm_1chip.xplane.pb``):

    python3 tests/bench_chip/record_ssm_trace.py <out.xplane.pb>

On one TPU chip: the tiny granite cell's train step
(``granite_helpers.tiny_granite``), fed by the data pipeline's
``Prefetcher``, warmed up, then two steps traced inside a ``bench/window``
span as the harness traces them; kept small by ``record_scoped_trace.strip``.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench_chip_scope_helpers import step_parts  # noqa: E402
from benchmarks.chip import trace_reduce as tr  # noqa: E402
from granite_helpers import tiny_granite  # noqa: E402
from record_scoped_trace import strip  # noqa: E402


def main(out: str) -> None:
    import jax

    from repro.data.pipeline import Prefetcher
    from repro.launch.train import build_step
    from repro.train import step as TS

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_ssm_trace.py: needs a TPU")
    cfg, shape, mesh, opts = step_parts(tiny_granite(seq=64, batch=2))
    with jax.set_mesh(mesh), tempfile.TemporaryDirectory() as logdir:
        step, bspecs = build_step(cfg, shape, mesh, opts)
        state = TS.init_state(cfg, jax.random.PRNGKey(0), mesh, opts)
        feed = Prefetcher(cfg, shape, mesh, bspecs, seed=1)
        try:
            for _ in range(3):
                state, _ = step(state, next(feed)[1])
            jax.block_until_ready(state)
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation("bench/window"):
                for _ in range(2):
                    state, _ = step(state, next(feed)[1])
                jax.block_until_ready(state)
            jax.profiler.stop_trace()
        finally:
            feed.close()
        with open(tr.find_xspace(logdir), "rb") as f:
            data = strip(f.read())
    with open(out, "wb") as f:
        f.write(data)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
