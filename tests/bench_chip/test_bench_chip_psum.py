"""The psum control cell (``whisper-medium.psum.4chip``) on four host
devices, at a CPU test's size and in f32 (XLA's CPU backend aborts
compiling the bf16 psum step): with XLA's psum over the pod axis the run
is ``correct``; with the exchange left out it is not.  Runs in a child
process, which sets the host device count before it imports jax."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = textwrap.dedent(
    """
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {here!r})
    import jax
    from bench_chip_helpers import harness, tiny_cell
    from repro.core import collectives

    if {broken!r}:  # the exchange left out: every chip keeps its own gradient
        collectives.grad_sync = lambda grads, *a, **k: grads
    cell = tiny_cell("whisper-medium.psum.4chip")
    assert cell.traffic["pod_sync"] == "psum" and cell.chips == 4
    r = harness.run(cell, 2**31 + 13, 0.5, False, jax.devices(), time.perf_counter())
    print(json.dumps({{"correct": r["correct"], "checks": r["checks"], "count": r["device"]["count"]}}))
    """
)


@pytest.mark.parametrize("broken", [False, True])
def test_psum_cell_is_correct_and_not_without_the_exchange(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(here=HERE, broken=broken)],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["count"] == 4
    assert r["correct"] is not broken, r["checks"]
