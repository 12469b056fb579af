"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Figures 5-9 run on the
discrete-event simulator (the real Hoplite control plane over a modeled
EC2 data plane); the chain-condition bench validates Appendix A; the TPU
collective bench reads compiled-HLO schedules.  Chip measurements are the
chip benchmark's (``benchmarks/chip/``, ``BENCHMARK.json``).

``--json PATH`` switches to the threaded *data-plane* suite
(``bench_core_dataplane``: real bytes through ``LocalCluster``) and
writes machine-readable results -- the tracked ``BENCH_core.json``
trajectory.  ``--quick`` shrinks payloads for CI smoke runs.
"""

from __future__ import annotations

import argparse
import sys
import traceback

sys.path.insert(0, "src")
sys.path.insert(0, ".")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json",
        metavar="PATH",
        default=None,
        help="run the core data-plane suite and write JSON results to PATH",
    )
    parser.add_argument(
        "--quick",
        action="store_true",
        help="smaller payloads (CI smoke mode); only affects --json suite",
    )
    args = parser.parse_args()

    if args.json:
        from benchmarks import bench_core_dataplane

        bench_core_dataplane.run(quick=args.quick, json_path=args.json)
        return

    from benchmarks import (
        bench_async,
        bench_chain_condition,
        bench_collectives,
        bench_core_dataplane,
        bench_p2p,
        bench_param_server,
        bench_rl,
        bench_serving_ensemble,
        bench_tpu_collectives,
    )

    sections = [
        ("Figure 5: point-to-point", bench_p2p.run),
        ("Figure 6: collective latency", bench_collectives.run),
        ("Figure 7: asynchrony", bench_async.run),
        ("Appendix A: chain condition", bench_chain_condition.run),
        ("Figure 8: parameter server", bench_param_server.run),
        ("Figure 9: RL throughput", bench_rl.run),
        ("Section 5.3: ensemble serving", bench_serving_ensemble.run),
        ("Threaded data plane (real bytes)", bench_core_dataplane.run),
        ("TPU collective schedules", bench_tpu_collectives.run),
    ]
    failures = 0
    for title, fn in sections:
        print(f"# --- {title} ---")
        try:
            fn()
        except BaseException:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
