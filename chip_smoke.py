"""Chip smoke test: the Hoplite-synced trainer, end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host of four chips (2x2)

One chip: whisper-medium at full width (d_model 1024, 24 encoder + 24
decoder layers, vocab 51865, random weights from ``--seed``) trains a few
steps on one repeated seeded batch of 8 x 448 tokens, through the
trainer's own ``repro.launch.train.build_step``.  It prints the compile
seconds, the step time after warm-up, every loss and the peak HBM, and
fails unless every loss is finite and the last is below the first.

Four chips runs only what exists across chips, and what it is compared with:

  (a) ``chain_allreduce``, ``two_level_allreduce`` and ``rs_ag_allreduce``
      under ``shard_map`` on a 4-device axis, f32 buckets of 4 KiB, 1 MiB
      and 64 MiB, each ``allclose`` to ``lax.psum``;
  (b) the whisper-medium train step on ``(pod=4, data=1, model=1)`` with
      ``pod_sync`` = hoplite_chain, hoplite_2d and psum, from the same
      state and batch: the losses agree to rounding.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU.  Anything else -- no TPU, a failed check, an error --
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core import collectives as C
from repro.data import pipeline
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import auto_mesh, make_mesh
from repro.launch.train import build_step
from repro.train import step as TS

ARCH = "whisper-medium"
SHAPE = ShapeSpec("smoke", seq_len=448, global_batch=8, kind="train")
BUCKET_BYTES = (4 << 10, 1 << 20, 64 << 20)
ALLREDUCES = {
    "chain_allreduce": C.chain_allreduce,
    "two_level_allreduce": C.two_level_allreduce,
    "rs_ag_allreduce": C.rs_ag_allreduce,
}
POD_SYNCS = tuple(s for s in TS.POD_SYNCS if s != "gspmd")  # the syncs over a pod axis
TRAIN_STEPS = 6  # two of them warm-up
POD_STEPS = 4  # per sync method; three methods share the 1200 s budget
LOSS_RTOL = 1e-2  # bf16 gradients summed in a different order


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def smoke_options(steps: int, pod_sync: str = "gspmd") -> TS.TrainOptions:
    """Short LR warm-up so a few steps on one batch visibly lower the loss."""
    adamw = dataclasses.replace(
        TS.TrainOptions().adamw, lr=1e-3, warmup_steps=2, total_steps=max(steps, 2)
    )
    return TS.TrainOptions(pod_sync=pod_sync, adamw=adamw)


def lower_step(cfg, shape, mesh, opts):
    """The trainer's jitted step lowered for ``mesh`` from abstract state and
    batch, so that several can compile at once; returns (lowered, batch specs)."""
    with jax.set_mesh(mesh):
        train_step, bspecs = build_step(cfg, shape, mesh, opts)
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            TS.abstract_state(cfg), TS.state_shardings(cfg, mesh, opts),
        )
        batch = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
            for k, v in pipeline.host_batch(cfg, shape, 0).items()
        }
        return train_step.lower(state, batch), bspecs


def compile_all(lowered: dict):
    """Compile every lowered program concurrently (XLA compiles outside the
    GIL); returns ({name: compiled}, wall seconds for all of them)."""
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as ex:
        futures = {k: ex.submit(l.compile) for k, l in lowered.items()}
        compiled = {k: f.result() for k, f in futures.items()}
    return compiled, time.perf_counter() - t0


def run_steps(compiled, cfg, shape, mesh, opts, bspecs, steps: int, warmup: int = 2,
              seed: int = 0):
    """``steps`` steps from a fresh state on one repeated batch.  Returns
    ``{"step_s", "losses"}``; ``step_s`` is the mean wall time of the steps
    after the first ``warmup``, each ended with ``block_until_ready``."""
    with jax.set_mesh(mesh):
        batch = pipeline.device_batch(cfg, shape, 0, mesh, bspecs, seed=seed)
        state = TS.init_state(cfg, jax.random.PRNGKey(seed), mesh, opts)
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = jax.block_until_ready(compiled(state, batch))
            times.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
        del state
    timed = times[warmup:] or times
    return {"step_s": sum(timed) / len(timed), "losses": losses}


def train_run(cfg, shape, mesh, opts, steps: int, seed: int = 0):
    """Compile, then ``run_steps``; adds ``compile_s`` to its result."""
    lowered, bspecs = lower_step(cfg, shape, mesh, opts)
    compiled, compile_s = compile_all({"step": lowered})
    r = run_steps(compiled["step"], cfg, shape, mesh, opts, bspecs, steps, seed=seed)
    return dict(r, compile_s=compile_s)


def check_training(losses) -> None:
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def check_allreduces(devices, bucket_bytes=BUCKET_BYTES, seed: int = 0, reps: int = 5):
    """Each Hoplite allreduce against ``lax.psum`` on one axis over ``devices``."""
    n = len(devices)
    mesh = auto_mesh((n,), ("x",), devices)
    rng = np.random.default_rng(seed)
    rows = []
    for nbytes in bucket_bytes:
        x = jax.device_put(
            rng.standard_normal((n, nbytes // 4), dtype=np.float32),
            NamedSharding(mesh, P("x")),
        )
        results = {}
        for name, fn in dict(ALLREDUCES, psum=lambda a, ax: jax.lax.psum(a, ax)).items():
            f = jax.jit(jax.shard_map(
                lambda a, fn=fn: fn(a, "x"), mesh=mesh, in_specs=P("x"), out_specs=P("x")
            ))
            out = jax.block_until_ready(f(x))  # compile + warm
            t = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(f(x))
                t.append(time.perf_counter() - t0)
            results[name] = (np.asarray(out), sorted(t)[len(t) // 2])
        want = results.pop("psum")
        for name, (got, sec) in results.items():
            err = float(np.max(np.abs(got - want[0])))
            ok = bool(np.allclose(got, want[0], rtol=1e-5, atol=1e-5))
            rows.append({"op": name, "bytes": nbytes, "max_abs_diff": err,
                         "median_s": sec, "psum_median_s": want[1], "allclose": ok})
            print(f"allreduce {name:20s} {nbytes:>9d} B: max|diff| vs psum {err:.3e} "
                  f"allclose={ok} median {sec * 1e3:.3f} ms (psum {want[1] * 1e3:.3f} ms)")
    bad = [r for r in rows if not r["allclose"]]
    check(not bad, f"allreduce differs from lax.psum: {bad}")
    return rows


def compare_pod_syncs(cfg, shape, devices, steps: int, seed: int = 0, syncs=POD_SYNCS):
    """The pod-synced train step under each sync method from one state and
    batch; every method's losses must match psum's to ``LOSS_RTOL``."""
    mesh = make_mesh(devices, pod=True)
    print(f"pod mesh: {dict(mesh.shape)}")
    opts = {sync: smoke_options(steps, sync) for sync in syncs}
    lowered = {sync: lower_step(cfg, shape, mesh, opts[sync]) for sync in syncs}
    compiled, compile_s = compile_all({sync: l for sync, (l, _) in lowered.items()})
    print(f"compiled {len(syncs)} pod-synced steps concurrently in {compile_s:.1f} s")
    runs = {}
    for sync in syncs:
        r = run_steps(compiled[sync], cfg, shape, mesh, opts[sync], lowered[sync][1], steps,
                      seed=seed)
        runs[sync] = r
        print(f"pod_sync={sync}: step {r['step_s'] * 1e3:.1f} ms, losses {r['losses']}")
        check_training(r["losses"])
    ref = np.asarray(runs["psum"]["losses"])
    for sync in syncs:
        rel = float(np.max(np.abs(np.asarray(runs[sync]["losses"]) - ref) / np.abs(ref)))
        runs[sync]["max_rel_diff_vs_psum"] = rel
        print(f"pod_sync={sync}: max relative loss difference vs psum {rel:.3e}")
        check(rel <= LOSS_RTOL, f"{sync} losses differ from psum's by {rel:.3e}")
    return dict(runs, compile_s=compile_s)


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def peak_hbm(devices):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    d0 = devices[0]
    print(f"devices: {len(devices)} x {d0.platform} {d0.device_kind}")
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {d0.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {len(devices)}", file=sys.stderr)
        return 1
    use = devices[: args.chips]
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries before)")

    cfg = get_config(ARCH)
    print(f"{cfg.name}: d_model {cfg.d_model}, {cfg.encoder_layers} encoder + "
          f"{cfg.num_layers} decoder layers, vocab {cfg.vocab_size}, "
          f"~{cfg.param_count() / 1e9:.2f} B params; batch {SHAPE.global_batch} x "
          f"{SHAPE.seq_len} tokens, {cfg.encoder_seq} encoder frames")
    summary = {}
    if args.chips == 1:
        r = train_run(cfg, SHAPE, make_mesh(use), smoke_options(TRAIN_STEPS), TRAIN_STEPS,
                      seed=args.seed)
        print(f"compile {r['compile_s']:.1f} s; step after warm-up {r['step_s'] * 1e3:.1f} ms")
        print(f"losses {r['losses']}")
        check_training(r["losses"])
        summary["train"] = r
    else:
        summary["allreduce"] = check_allreduces(use, seed=args.seed)
        summary["pod_sync"] = compare_pod_syncs(cfg, SHAPE, use, POD_STEPS, args.seed)
    summary["peak_bytes_in_use"] = peak_hbm(use)
    print(f"peak HBM in use: {[f'{b / 2**30:.2f} GiB' if b else b for b in summary['peak_bytes_in_use']]}")
    print(f"compile cache: {cache_entries(cache_dir)} entries after")
    print("summary " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
