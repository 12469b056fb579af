"""One run of one benchmark cell: set-up, the checked steps, the timed
window, the optional trace, and the comparison with the plain reference.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``configs/<config>.json``: the model as run (``program`` holds the
  trainer's ``ModelConfig`` fields), which ``reference/<reference>.py``
  and ``flops/<flops>.py`` the configuration uses, and its source;
* ``traffic/<traffic>.json``: batch, sequence, sync, remat and optimizer;
* ``limits/<workload>.json``: the limit of each number compared;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``;
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

The window drives the trainer's own path: the jitted step of
``repro.launch.train.build_step`` fed by ``repro.data.pipeline.Prefetcher``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from collections import deque
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_traces")
NOT_FINITE = 1e300  # a gap that could not be read (a missing or non-finite number)
TRACE_STEPS = 2  # steps in the traced segment that follows the window with --trace 1
ZERO_GRAD_FRAC = 1e-3  # leaves whose reference gradient is below this share of the median are left out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, by file path."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_chip_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench_path: str = os.path.join(ROOT, "BENCHMARK.json")) -> Cell:
    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload,
        chips=w["chips"],
        config=_json("configs", w["config"] + ".json"),
        traffic=_json("traffic", w["traffic"] + ".json"),
        limits=_json("limits", workload + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def enable_compile_cache() -> str:
    """The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (jax reads it itself), else ``.jax_cache/`` at the checkout root."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def model_config(program: dict):
    from repro.configs.base import LayerSpec, ModelConfig

    fields = dict(program)
    fields["pattern"] = tuple(LayerSpec(**p) for p in fields["pattern"])
    return ModelConfig(**fields)


def _norms(tree, paths):
    import jax.numpy as jnp

    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for p, x in zip(paths, tree)}


def compare(program_reading: dict, ref: dict) -> Dict[str, float]:
    """The three numbers compared: each the worst gap between the program's
    reading and the reference's.

    * ``loss_gap``: over the checked steps, |loss - ref| / ref;
    * ``grad_gap``: over leaves, the gap of step 1's gradient norms;
    * ``update_gap``: over leaves, the gap of |params after the checked
      steps - initial params|.

    A leaf's gap is measured against the larger of its reference norm and the
    median leaf's.  Leaves whose reference gradient is under
    ``ZERO_GRAD_FRAC`` of the median leaf's move by round-off alone and are
    left out of both.
    """
    rl, pl = ref["losses"], program_reading["losses"]
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(pl, rl)) if len(pl) == len(rl) else math.inf
    rg, rc = ref["grad_norms"], ref["change_norms"]
    med_g = statistics.median(rg.values())
    keep = [k for k, v in rg.items() if v >= ZERO_GRAD_FRAC * med_g]
    med_c = statistics.median(rc[k] for k in keep)

    def worst(prog: dict, refn: dict, med: float) -> float:
        if set(prog) != set(refn):
            return math.inf
        return max(abs(prog[k] - refn[k]) / max(refn[k], med) for k in keep)

    gaps = {"loss_gap": loss_gap,
            "grad_gap": worst(program_reading["grad_norms"], rg, med_g),
            "update_gap": worst(program_reading["change_norms"], rc, med_c)}
    return {k: (v if math.isfinite(v) else NOT_FINITE) for k, v in gaps.items()}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float,
        build_step: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result object the benchmark prints.

    ``build_step`` defaults to the trainer's ``repro.launch.train.build_step``;
    the self-tests pass a broken one to see ``correct`` come out false."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import trace_reduce, weights
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import Prefetcher
    from repro.launch import train as launch_train
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train import step as TS

    build_step = build_step or launch_train.build_step
    program, traffic = cell.config["program"], cell.traffic
    reference = load_module("reference", cell.config["reference"])
    cfg = model_config(program)
    shape = ShapeSpec(cell.name, traffic["seq_len"], traffic["global_batch"], "train")
    use = list(devices[: cell.chips])
    mesh = make_mesh(use, pod=traffic["pod_sync"] != "gspmd")
    opts = TS.TrainOptions(num_microbatches=traffic["microbatches"], remat=traffic["remat"],
                           pod_sync=traffic["pod_sync"], adamw=AdamWConfig(**traffic["adamw"]))
    checked = traffic["checked_steps"]
    key = weights.base_key(seed)

    with jax.set_mesh(mesh):
        train_step, bspecs = build_step(cfg, shape, mesh, opts)
        abstract = TS.abstract_state(cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(abstract["params"])
        paths = [weights.path_str(kp) for kp, _ in flat]
        layout = {p: tuple(a.shape) for p, (_, a) in zip(paths, flat)}
        if layout != reference.param_spec(program):
            raise ValueError(f"the program's parameters differ from the configuration's: "
                             f"{sorted(set(layout) ^ set(reference.param_spec(program)))}")

        def make_state(k):
            zeros = lambda a: jnp.zeros(a.shape, a.dtype)
            return {"params": weights.tree_values(k, abstract["params"]),
                    "opt": jax.tree.map(zeros, abstract["opt"]),
                    "step": zeros(abstract["step"])}

        state = jax.jit(make_state, out_shardings=TS.state_shardings(cfg, mesh, opts))(key)
        m_norms = jax.jit(lambda m: _norms(jax.tree.leaves(m), paths))
        change_norms = jax.jit(lambda k, p: _norms(
            [x.astype(jnp.float32) - weights.leaf_value(k, path, x.shape, x.dtype).astype(jnp.float32)
             for path, x in zip(paths, jax.tree.leaves(p))], paths))

        feed = Prefetcher(cfg, shape, mesh, bspecs, start_step=0, seed=seed)
        try:
            # Set-up: compile, then the checked steps through the window's own call and feed.
            losses, grad_norms = [], {}
            for t in range(checked):
                _, batch = next(feed)
                state, metrics = train_step(state, batch)
                losses.append(float(metrics["loss"]))
                if t == 0:
                    scale = min(1.0, opts.adamw.clip_norm / (float(metrics["grad_norm"]) + 1e-9))
                    grad_norms = {p: float(v) / (1 - opts.adamw.b1) / scale
                                  for p, v in m_norms(state["opt"]["m"]).items()}
            reading = {"losses": losses, "grad_norms": grad_norms,
                       "change_norms": {p: float(v) for p, v in
                                        change_norms(key, state["params"]).items()}}
            setup_s = time.perf_counter() - t_start

            inflight: deque = deque()
            window_losses = []

            def one_step():
                nonlocal state, metrics
                with jax.profiler.TraceAnnotation("bench/data_wait"):
                    w0 = time.perf_counter()
                    _, batch = next(feed)
                    waited = time.perf_counter() - w0
                with jax.profiler.TraceAnnotation("bench/dispatch"):
                    state, metrics = train_step(state, batch)
                window_losses.append(metrics["loss"])
                inflight.append(metrics["loss"])
                if len(inflight) > 1:  # keep one step queued behind the running one
                    with jax.profiler.TraceAnnotation("bench/wait"):
                        inflight.popleft().block_until_ready()
                return waited

            steps, data_wait = 0, 0.0
            t0 = time.perf_counter()
            while True:
                data_wait += one_step()
                steps += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(state)
            window_s = time.perf_counter() - t0

            trace_dir = os.path.join(TRACE_DIR, cell.name)
            if trace:  # a separate traced segment after the window
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation("bench/window"):
                    for _ in range(TRACE_STEPS):
                        one_step()
                    with jax.profiler.TraceAnnotation("bench/wait"):
                        jax.block_until_ready(state)
                jax.profiler.stop_trace()
        finally:
            feed.close()
            feed.thread.join(timeout=60)
            feed.close()  # a batch the producer put while it stopped
        failed = sum(1 for l in window_losses[:steps] if not math.isfinite(float(l)))
        stats = [d.memory_stats() or {} for d in use]
        peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
        del state, metrics, window_losses, inflight
    gc.collect()

    reduced = None
    t1 = time.perf_counter()
    if trace:
        reduced = trace_reduce.reduce(
            *trace_reduce.load(trace_reduce.find_xspace(trace_dir)), n_chips=cell.chips)
    t2 = time.perf_counter()
    ref = reference.run(program, traffic, seed, use[0], steps=checked)
    print(f"timing setup {setup_s:.1f} s, window {window_s:.1f} s ({steps} steps), "
          f"trace reduction {t2 - t1:.1f} s, reference {time.perf_counter() - t2:.1f} s",
          file=sys.stderr)
    gaps = compare(reading, ref)
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in gaps.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    d0 = use[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(use),
              "memory_peak_bytes": int(peak_bytes)}
    if trace:
        ctx = {"steps": steps, "window_s": window_s, "data_wait_s": data_wait,
               "trace_steps": TRACE_STEPS,
               "model_flops_per_step": load_module("flops", cell.config["flops"]).model_flops(
                   program, traffic),
               "chips": cell.chips, "device_kind": d0.device_kind,
               "peaks": _json("peaks.json"), "trace": reduced, "peak_bytes": peak_bytes}
        metrics_out = {}
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        e2e = {"step_ms": window_s / steps * 1e3, "setup_s": setup_s}
        metrics_out = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics_out, "device": device}
    if reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["readings"] = {"program": reading, "reference": ref}
    result["checks"] = checks
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines on stderr, the result as the
    last line on stdout (``readings`` go to stderr only: they are long)."""
    readings = result.pop("readings", None)
    if readings is not None:
        print("readings " + json.dumps(readings), file=err)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
