"""The granite cell end to end through the harness on the CPU at a tiny
size (``granite_helpers``): the sound run is ``correct``, and the fp8
control (the reference a precision below the configuration's bf16) is not.
Its own file, so that a test worker runs it beside the other granite tests."""

import time

import jax

from bench_chip_helpers import harness
from benchmarks.chip import calibrate
from granite_helpers import tiny_granite

SEED = 2**31 + 11  # seeds run past 32 bits


def test_sound_run_is_correct_and_the_fp8_control_is_not():
    cell = tiny_granite()
    result = harness.run(cell, SEED, 0.5, False, jax.devices(), time.perf_counter())
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    gaps = calibrate.readings(cell, [3], ["fp8"], jax.devices()[0])["fp8"][0]
    assert any(gaps[k] > cell.limits[k] for k in gaps), gaps
