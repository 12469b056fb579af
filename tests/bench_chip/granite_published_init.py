"""The granite cell's comparison with the reference under Mamba-2's
published initialization of the decays, so that the state lives across
SSD chunks: A_log = log(A) with A uniform in [1, 16], and dt_bias the
inverse softplus of dt, log-uniform in [0.001, 0.1].  The benchmark's own
N(0, 0.02^2) weights give A near -1 and dt near 0.69, a state that lives a
few positions.  Also the reference with the state dropped between chunks
(``fault="chunk_reset"``) against the sound reference, under the same init.

    python3 tests/bench_chip/granite_published_init.py --seed 7 [--tiny]

On one TPU chip at the cell's size (``--tiny``: on any device at a CPU
test's size).  Prints one JSON line: the run's checks and the fault's gaps.
"""

import argparse
import json
import os
import sys
import time
import zlib

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench_chip_helpers import harness  # noqa: E402
from benchmarks.chip import weights  # noqa: E402
from granite_helpers import WORKLOAD, tiny_granite  # noqa: E402


def published(leaf_value):
    """``weights.leaf_value`` with Mamba-2's published A_log and dt_bias."""
    import jax
    import jax.numpy as jnp

    def value(key, path, shape, dtype):
        k = jax.random.fold_in(key, zlib.crc32(("published/" + path).encode()))
        if path.endswith("/A_log"):
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
        if path.endswith("/dt_bias"):
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        return leaf_value(key, path, shape, dtype)

    return value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    import jax

    weights.leaf_value = published(weights.leaf_value)
    harness.enable_compile_cache()
    cell = tiny_granite() if args.tiny else harness.load_cell(WORKLOAD)
    result = harness.run(cell, args.seed, 2.0, False, jax.devices(), T_START)
    ref = result["readings"]["reference"]
    reference = harness.load_module("reference", cell.config["reference"])
    program, traffic = cell.config["program"], cell.traffic
    fault = reference.run(program, traffic, args.seed, jax.devices()[0], traffic["checked_steps"],
                          fault="chunk_reset")
    print(json.dumps({"seed": args.seed, "device": result["device"], "correct": result["correct"],
                      "checks": result["checks"], "losses": ref["losses"],
                      "chunk_reset": harness.compare(fault, ref)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
