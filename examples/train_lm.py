"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

A qwen3-family config scaled to ~100M params, trained on the deterministic
synthetic pipeline with the production train step (FSDP x TP mesh,
microbatched grad accumulation, remat, async checkpointing), including a
mid-run simulated crash + restart from checkpoint.

Run (a CPU-only tool: it forces 4 host devices before jax is imported):
    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import dataclasses
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, "src")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import get_config
from repro.configs.base import LayerSpec, ShapeSpec
from repro.data import pipeline
from repro.launch.mesh import make_mesh
from repro.sharding import partitioning
from repro.train import step as TS


def lm_100m():
    """qwen3-family config at ~100M params (12L x 512 x 8H, vocab 8k)."""
    base = get_config("qwen3-14b")
    return dataclasses.replace(
        base,
        name="qwen3-100m",
        num_layers=12,
        d_model=512,
        num_heads=8,
        num_kv_heads=4,
        d_ff=2048,
        vocab_size=8192,
        head_dim=64,
        dtype="float32",
        param_dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm_ckpt")
    ap.add_argument("--crash-at", type=int, default=120,
                    help="simulate a failure at this step (0 = off)")
    args = ap.parse_args()

    cfg = lm_100m()
    from repro.models.common import param_elems
    from repro.models.transformer import model_skel

    print(f"model: {cfg.name}, {param_elems(model_skel(cfg))/1e6:.1f}M params")
    shape = ShapeSpec("lm100m", seq_len=64, global_batch=4, kind="train")
    mesh = make_mesh(model=2)  # (data=2, model=2) on the 4 host devices
    opts = TS.TrainOptions(
        num_microbatches=1,
        adamw=dataclasses.replace(TS.TrainOptions().adamw, lr=1e-3, warmup_steps=30,
                                  total_steps=args.steps),
    )

    import shutil

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    def run_until(stop_step):
        """(Re)start training from the latest checkpoint up to stop_step."""
        with jax.set_mesh(mesh):
            shardings = TS.state_shardings(cfg, mesh, opts)
            ckpt = Checkpointer(args.ckpt_dir)
            start = 0
            if ckpt.latest_step() is not None:
                start, state = ckpt.restore(TS.abstract_state(cfg), shardings=shardings)
                print(f"[restart] resumed at step {start}")
            else:
                state = TS.init_state(cfg, jax.random.PRNGKey(0), mesh, opts)
            train_step = jax.jit(
                TS.make_train_step(cfg, mesh, shape, opts),
                in_shardings=(shardings, None),
                out_shardings=(shardings, None),
                donate_argnums=(0,),
            )
            bspecs = partitioning.batch_specs(cfg, mesh, shape, opts.sharding)
            losses = []
            t0 = time.time()
            for step_idx in range(start, stop_step):
                batch = pipeline.device_batch(cfg, shape, step_idx, mesh, bspecs, structured=True)
                state, metrics = train_step(state, batch)
                losses.append(float(metrics["loss"]))
                if (step_idx + 1) % 25 == 0:
                    tokps = (step_idx + 1 - start) * shape.global_batch * shape.seq_len / (
                        time.time() - t0
                    )
                    print(f"  step {step_idx+1}: loss={losses[-1]:.4f} tok/s={tokps:.0f}")
                if (step_idx + 1) % 50 == 0:
                    ckpt.save_async(step_idx + 1, state)
            ckpt.save(stop_step, state)
            ckpt.wait()
            return losses

    first_loss = None
    if args.crash_at and args.crash_at < args.steps:
        losses = run_until(args.crash_at)
        first_loss = losses[0]
        print(f"[crash] simulating process loss at step {args.crash_at}")
        losses2 = run_until(args.steps)
        final = losses2[-1]
    else:
        losses = run_until(args.steps)
        first_loss, final = losses[0], losses[-1]
        losses2 = losses
    print(f"loss: {first_loss:.3f} -> {final:.3f} over {args.steps} steps "
          f"(must decrease on a learnable synthetic stream)")
    assert final < first_loss, "loss did not improve"
    print("train_lm OK")


if __name__ == "__main__":
    main()
