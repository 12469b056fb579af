"""One run of one benchmark cell on the TPU chips of this machine.

    python3 benchmarks/chip/run.py --workload whisper-medium.train.1chip \\
        --seed 7 --seconds 10 --trace 0

Cells, configurations, traffic, limits and per-layer metrics are found by
name from ``BENCHMARK.json`` (see ``harness.py``).  The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics with ``--trace 0``, the per-layer ones with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers are the
last lines of stderr.  With no TPU, or fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log to a fixed /tmp path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, jax found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, jax found {len(devices)}",
              file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
