"""Records the small scoped chip trace that ``test_bench_chip_scopes.py``
reads (``benchmarks/chip/testdata/small_scoped_1chip.xplane.pb``):

    python3 tests/bench_chip/record_scoped_trace.py <out.xplane.pb>

On one TPU chip: the tiny starcoder2-3b-l6 cell's train step (every width
cut as ``bench_chip_helpers.tiny_cell`` cuts it), fed by the data
pipeline's ``Prefetcher``, warmed up, then two steps traced inside a
``bench/window`` span as the harness traces them.  The file keeps what the
readers use (``strip``), so that it stays small.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench_chip_helpers import tiny_cell  # noqa: E402
from bench_chip_scope_helpers import step_parts  # noqa: E402
from benchmarks.chip import scopes  # noqa: E402
from benchmarks.chip import trace_reduce as tr  # noqa: E402


def _keep(repeated, pred) -> None:
    kept = []
    for x in repeated:
        if pred(x):
            kept.append(type(x)())
            kept[-1].CopyFrom(x)
    del repeated[:]
    repeated.extend(kept)


def strip(data: bytes) -> bytes:
    """The trace with what the readers use: the chips' lines (XProf groups
    ops by the ``XLA Modules`` and ``Steps`` lines), each op's name and
    ``tf_op``, and the host's ``bench/window`` and ``data/produce`` spans;
    the fields ``scopes``' schema does not name are dropped."""
    space = scopes._schema()()
    space.ParseFromString(data)
    space.DiscardUnknownFields()
    _keep(space.planes, lambda p: tr.DEVICE_PLANE.match(p.name) or p.name == tr.HOST_PLANE)
    for plane in space.planes:
        if plane.name == tr.HOST_PLANE:
            spans = {e.key for e in plane.event_metadata
                     if e.value.name in (tr.WINDOW_SPAN, scopes.PRODUCE_SPAN)}
            for line in plane.lines:
                _keep(line.events, lambda e: e.metadata_id in spans)
            _keep(plane.lines, lambda line: len(line.events))
        tf_op = {e.key for e in plane.stat_metadata if e.value.name == scopes.TF_OP}
        _keep(plane.stat_metadata, lambda e: e.key in tf_op)
        for e in plane.event_metadata:
            _keep(e.value.stats, lambda st: st.metadata_id in tf_op)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        _keep(plane.event_metadata, lambda e: e.key in used)
    return space.SerializeToString()


def main(out: str) -> None:
    import jax

    from repro.data.pipeline import Prefetcher
    from repro.launch.train import build_step
    from repro.train import step as TS

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scoped_trace.py: needs a TPU")
    cfg, shape, mesh, opts = step_parts(tiny_cell("starcoder2-3b-l6.train.1chip"))
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
