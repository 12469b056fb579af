"""Readings that set a cell's limits: the control and the planted faults,
each put in the program's place and compared with the f32 reference by the
same numbers a run compares (``harness.compare``).  Not run by the
benchmark's own runs.

    python3 benchmarks/chip/calibrate.py --workload whisper-medium.train.1chip \\
        --seeds 11 12 13 --variants fp8 half local:4

Variants: ``fp8`` is the control (the reference with every matmul in scaled
float8_e4m3fn, the precision below the configuration's bfloat16); ``half``
takes the loss and gradient over half of the batch; ``local:n`` takes the
gradient over the first 1/n of the rows, as a chip of n would without the
exchange.  A step that returns its state unchanged reads 1 by
``frozen_gaps`` and needs no run.  Prints one JSON line per seed and
variant, and last the smallest reading of each number per variant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path[:0] = [_root, os.path.join(_root, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def variant_reading(reference, program: dict, traffic: dict, seed: int, device, variant: str):
    steps = traffic["checked_steps"]
    if variant == "fp8":
        return reference.run(program, traffic, seed, device, steps, precision="fp8")
    return reference.run(program, traffic, seed, device, steps, fault=variant)


def frozen_gaps(ref: dict) -> dict:
    """A step that returns its state unchanged: no moment, no change."""
    from benchmarks.chip import harness

    frozen = {"losses": [ref["losses"][0]] * len(ref["losses"]),
              "grad_norms": {k: 0.0 for k in ref["grad_norms"]},
              "change_norms": {k: 0.0 for k in ref["change_norms"]}}
    return harness.compare(frozen, ref)


def readings(cell, seeds, variants, device) -> dict:
    """{variant: [gaps per seed]}, with the sound reference's own readings."""
    from benchmarks.chip import harness

    reference = harness.load_module("reference", cell.config["reference"])
    program, traffic = cell.config["program"], cell.traffic
    out = {v: [] for v in list(variants) + ["frozen"]}
    for seed in seeds:
        ref = reference.run(program, traffic, seed, device, traffic["checked_steps"])
        out["frozen"].append(frozen_gaps(ref))
        for v in variants:
            gaps = harness.compare(variant_reading(reference, program, traffic, seed, device, v), ref)
            out[v].append(gaps)
            print(json.dumps({"seed": seed, "variant": v, "gaps": gaps}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["fp8", "half"])
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    import jax

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    out = readings(cell, args.seeds, args.variants, jax.devices()[0])
    least = {v: {k: min(g[k] for g in gs) for k in gs[0]} for v, gs in out.items()}
    print(json.dumps({"workload": args.workload, "least": least}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
