"""The harness end to end on the CPU at a tiny size, past its look for a
chip: a sound run is ``correct``; a broken timed path is not.  Also the
control: the reference in fp8 fails the cells' limits."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench_chip_helpers import harness, tiny_cell
from benchmarks.chip import calibrate
from repro.configs.base import ShapeSpec
from repro.launch.train import build_step

WORKLOADS = ["whisper-medium.train.1chip", "starcoder2-3b-l6.train.1chip"]
SEED = 2**31 + 7  # seeds run past 32 bits


def _run(cell, build=None):
    return harness.run(cell, SEED, 0.5, False, jax.devices(), time.perf_counter(), build)


def frozen_step(cfg, shape, mesh, opts):
    """A step that returns its state unchanged (and the real metrics)."""
    step, bspecs = build_step(cfg, shape, mesh, opts)

    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    return f, bspecs


def half_batch_step(cfg, shape, mesh, opts):
    """Half of the batch left out: the mean is taken over the other half."""
    half = ShapeSpec(shape.name, shape.seq_len, shape.global_batch // 2, shape.kind)
    step, _ = build_step(cfg, half, mesh, opts)
    _, bspecs = build_step(cfg, shape, mesh, opts)

    def f(state, batch):
        return step(state, {k: v[: shape.global_batch // 2] for k, v in batch.items()})

    return f, bspecs


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    result = _run(tiny_cell(workload))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", [frozen_step, half_batch_step])
def test_broken_step_is_not_correct(fault):
    result = _run(tiny_cell(WORKLOADS[0]), fault)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_fp8_control_fails_a_limit(workload):
    cell = tiny_cell(workload)
    out = calibrate.readings(cell, [3], ["fp8"], jax.devices()[0])
    gaps = out["fp8"][0]
    assert any(gaps[k] > cell.limits[k] for k in gaps), gaps
    assert all(v == 1.0 for k, v in out["frozen"][0].items() if k != "loss_gap")
