"""Quickstart: Hoplite in 60 seconds.

1. An in-process Hoplite cluster: Put / Get / Reduce with real bytes --
   watch the receiver-driven broadcast tree emerge and the reduce chain
   stream partial results.
2. The same schedules as TPU collectives (8 host devices): the paper's
   chain allreduce vs XLA's psum, bit-identical results.

Run (a CPU-only tool: it forces 8 host devices before jax is imported):
    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def object_store_demo():
    from repro.core.local import LocalCluster

    print("== Hoplite object store (8 in-process nodes) ==")
    cluster = LocalCluster(8, chunk_size=8192, pace=0.0002)

    # Put once, Get from 7 receivers: the broadcast tree builds itself.
    x = np.random.RandomState(0).rand(200_000).astype(np.float32)
    cluster.put(0, "weights", x)
    futs = [cluster.get_async(i, "weights") for i in range(1, 8)]
    for f in futs:
        np.testing.assert_array_equal(f.result(timeout=30), x)
    relays = [i for i, b in enumerate(cluster.bytes_sent_per_node) if b > 0 and i != 0]
    print(f"   broadcast delivered to 7 receivers; relay nodes (not the "
          f"producer!): {relays}")
    print(f"   per-node egress bytes: {cluster.bytes_sent_per_node}")

    # Dynamic reduce: contributions arrive in arbitrary order, chain adapts.
    grads = [np.random.RandomState(i).rand(50_000).astype(np.float64) for i in range(8)]
    for i, g in enumerate(grads):
        cluster.put(i, f"grad{i}", g)
    cluster.reduce(3, "sum", [f"grad{i}" for i in range(8)])
    np.testing.assert_allclose(cluster.get(3, "sum"), sum(grads), rtol=1e-12)
    print("   chained Reduce across 8 nodes: exact")

    # Fault tolerance: kill a node holding the only extra copy; re-fetch.
    cluster.fail_node(1)
    y = cluster.get(5, "weights", timeout=30)
    np.testing.assert_array_equal(y, x)
    print("   node 1 killed mid-flight; Get(5) recovered from surviving copies")


def tpu_collectives_demo():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C
    from repro.launch.mesh import auto_mesh

    print("== Hoplite chain schedules as TPU collectives (8 devices) ==")
    mesh = auto_mesh((8,), ("x",))
    x = np.random.RandomState(1).rand(8, 4096).astype(np.float32)

    def run(fn):
        g = jax.shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
        with jax.set_mesh(mesh):
            return np.asarray(jax.jit(g)(x))

    psum = run(lambda a: jax.lax.psum(a, "x"))
    chain = run(lambda a: C.chain_allreduce(a, "x", num_chunks=8))
    chain2d = run(lambda a: C.two_level_allreduce(a, "x", num_chunks=8))
    ring = run(lambda a: C.rs_ag_allreduce(a, "x"))
    for name, out in [("fused chain (paper)", chain), ("2-D chain", chain2d),
                      ("ring RS+AG", ring)]:
        np.testing.assert_allclose(out, psum, rtol=1e-5)
        print(f"   {name:20s} == lax.psum  (max |diff| "
              f"{np.abs(out - psum).max():.2e})")
    from repro.core.planner import ICI_LINK, use_two_dimensional
    for size, n in [(64 << 10, 256), (64 << 20, 256)]:
        sel = "2-D" if use_two_dimensional(n, ICI_LINK, size) else "1-D"
        print(f"   nBL>S rule: {size >> 10} KiB over {n} chips -> {sel} chain")


if __name__ == "__main__":
    object_store_demo()
    tpu_collectives_demo()
    print("quickstart OK")
