"""End-to-end training driver with checkpoint/restart + fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch whisper-medium \
        --steps 20 --seq-len 448 --global-batch 8 --ckpt-dir ckpt

The mesh covers every device jax reports (``launch/mesh.make_mesh``): one
chip trains on ``(data=1, model=1)``.  ``--pod-sync hoplite_chain``
(or ``hoplite_2d`` / ``psum``) puts a pod axis over all devices and syncs
gradients across it with that schedule; ``gspmd`` (the default) leaves the
sync to XLA.  On the CPU, several devices come from the caller's
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; this driver sets
no flags.

Production semantics:
  * deterministic data pipeline resumed by STEP INDEX, not iterator state;
  * async checkpointing every --ckpt-every steps (training overlaps the
    serialization), atomic directory renames;
  * automatic RESTART: if the checkpoint dir has a valid step, training
    resumes from it -- kill the process anywhere and rerun the command;
  * ELASTIC rescale: restore onto a mesh of a different size than the one
    that wrote the checkpoint (host numpy is the interchange format);
  * straggler note: synchronous SPMD has no per-step straggler slack;
    straggler mitigation lives in the task-runtime examples (async PS).

On the CPU this trains the ``--reduced`` configs; ``chip_smoke.py`` drives
the same functions at full width on a TPU.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.data import pipeline
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.sharding import partitioning
from repro.train import step as TS


def build_step(cfg: ModelConfig, shape: ShapeSpec, mesh, opts: TS.TrainOptions):
    """(jitted train step with the state donated, batch PartitionSpecs)."""
    # The step's named scopes (train/step.py: SCOPES) live in its ops' metadata,
    # which the persistent compile cache leaves out of its key by default: an
    # executable cached from the same program without them would come back
    # unnamed.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    shardings = TS.state_shardings(cfg, mesh, opts)
    train_step = jax.jit(
        TS.make_train_step(cfg, mesh, shape, opts),
        in_shardings=(shardings, None),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )
    return train_step, partitioning.batch_specs(cfg, mesh, shape, opts.sharding)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true", help="reduced (smoke) config")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--pod-sync", default="gspmd", choices=TS.POD_SYNCS,
                    help="anything but gspmd puts a pod axis over all devices")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    shape = ShapeSpec("cli", args.seq_len, args.global_batch, "train")
    mesh = make_mesh(pod=args.pod_sync != "gspmd")
    opts = TS.TrainOptions(
        num_microbatches=args.microbatches, pod_sync=args.pod_sync
    )

    with jax.set_mesh(mesh):
        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        start_step = 0
        if ckpt and ckpt.latest_step() is not None:
            state_like = TS.abstract_state(cfg)
            start_step, state = ckpt.restore(
                state_like, shardings=TS.state_shardings(cfg, mesh, opts)
            )
            print(f"[restart] resumed from checkpoint step {start_step}")
        else:
            state = TS.init_state(cfg, jax.random.PRNGKey(0), mesh, opts)

        train_step, bspecs = build_step(cfg, shape, mesh, opts)
        feed = pipeline.Prefetcher(cfg, shape, mesh, bspecs, start_step=start_step)
        t0 = time.time()
        tokens_done = 0
        try:
            for step_idx, batch in feed:
                if step_idx >= args.steps:
                    break
                with jax.profiler.StepTraceAnnotation("train", step_num=step_idx):
                    state, metrics = train_step(state, batch)
                tokens_done += shape.global_batch * shape.seq_len
                if (step_idx + 1) % args.log_every == 0:
                    dt = time.time() - t0
                    print(
                        f"step {step_idx+1}: loss={float(metrics['loss']):.4f} "
                        f"gnorm={float(metrics['grad_norm']):.3f} "
                        f"lr={float(metrics['lr']):.2e} "
                        f"tok/s={tokens_done/dt:.0f}"
                    )
                if ckpt and (step_idx + 1) % args.ckpt_every == 0:
                    ckpt.save_async(step_idx + 1, state)
        finally:
            feed.close()
        if ckpt:
            ckpt.save(args.steps, state)
            ckpt.wait()
            print(f"[ckpt] final checkpoint at step {args.steps}")
        print(f"done: {args.steps} steps, loss={float(metrics['loss']):.4f}")
        return state


if __name__ == "__main__":
    main()
