"""TPU-native Hoplite collectives: HLO link-byte + step-count comparison.

A CPU-only tool (it forces 8 host devices before jax is imported): it
compares the *compiled schedules* (the dry-run methodology): for a gradient-sized tensor on an
8-way axis, lower each allreduce implementation and report

  * collective-permute / all-reduce link bytes per device (HLO walk),
  * modeled completion time on ICI and on DCN constants
    (bytes / link_bw + steps * effective latency),

for: XLA psum, Hoplite fused chain (paper), Hoplite 2-D chain, ring
reduce-scatter+all-gather (beyond-paper), and the int8-compressed chain.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, "src")

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import MB, emit
from repro.core import collectives as C
from repro.core.planner import DCN_LINK, ICI_LINK
from repro.launch import hlo_cost
from repro.launch.mesh import auto_mesh

SIZE_ELEMS = 8 * MB // 4  # a 8 MB f32 gradient bucket


def lower_and_walk(fn, n=8):
    mesh = auto_mesh((n,), ("x",))
    x = jax.ShapeDtypeStruct((n, SIZE_ELEMS), jnp.float32)
    g = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    with jax.set_mesh(mesh):
        compiled = jax.jit(g).lower(x).compile()
    return hlo_cost.analyze(compiled.as_text())


def modeled_time(res, link, steps_estimate):
    bytes_ = res["collective_link_bytes"]
    return bytes_ / link.bandwidth + steps_estimate * (link.latency + 2e-6)


def run() -> None:
    n = 8
    cases = {
        "psum": lambda x: jax.lax.psum(x, "x"),
        "hoplite_chain": lambda x: C.chain_allreduce(x, "x", num_chunks=16),
        "hoplite_2d": lambda x: C.two_level_allreduce(x, "x", num_chunks=16),
        "rs_ag_ring": lambda x: C.rs_ag_allreduce(x, "x"),
    }
    steps = {
        "psum": 2 * (n - 1),
        "hoplite_chain": 16 + 2 * n - 3,
        "hoplite_2d": 2 * (16 + 2 * 3),
        "rs_ag_ring": 2 * (n - 1),
    }
    for name, fn in cases.items():
        res = lower_and_walk(fn, n)
        t_ici = modeled_time(res, ICI_LINK, steps[name])
        t_dcn = modeled_time(res, DCN_LINK, steps[name])
        emit(
            f"tpu_allreduce_{name}_linkbytes",
            res["collective_link_bytes"] / 1e6,  # MB, reported in us column
            f"ici_model={t_ici*1e6:.0f}us dcn_model={t_dcn*1e6:.0f}us "
            f"kinds={sorted(res['collectives_by_kind'])}",
        )


if __name__ == "__main__":
    run()
