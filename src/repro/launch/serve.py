"""Serving driver: load (or init) a model, run batched generation.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 16

The mesh is ``(data=n, model=1)`` over every device jax reports.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.checkpoint.checkpoint import Checkpointer
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.common import init_params
from repro.serving.engine import Engine, ServeOptions
from repro.train import step as TS


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = make_mesh()
    with jax.set_mesh(mesh):
        shardings = TS.state_shardings(cfg, mesh)["params"]
        if args.ckpt_dir:
            ckpt = Checkpointer(args.ckpt_dir)
            _, state = ckpt.restore(TS.abstract_state(cfg), shardings=TS.state_shardings(cfg, mesh))
            params = state["params"]
            print(f"[serve] restored params from {args.ckpt_dir}")
        else:
            params = init_params(T.model_skel(cfg), jax.random.PRNGKey(0))
            params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        T.set_activation_sharding(("data",), "model")
        eng = Engine(cfg, mesh, params, ServeOptions(max_seq=args.max_seq, batch_size=args.batch))
        rng = np.random.RandomState(0)
        batch = {
            "tokens": jnp.asarray(
                rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
            )
        }
        if cfg.is_encoder_decoder:
            batch["encoder_frames"] = jnp.asarray(
                rng.randn(args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32
            )
        t0 = time.time()
        out = eng.generate(batch, args.new_tokens)
        dt = time.time() - t0
        print(f"generated {out.shape} tokens in {dt:.2f}s "
              f"({args.batch*args.new_tokens/dt:.1f} tok/s)")
        print("first row:", out[0][:16])


if __name__ == "__main__":
    main()
