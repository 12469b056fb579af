"""The four-chip cell's path on four host devices: with the Hoplite chain
the run is ``correct``; with the exchange between chips left out (each chip
keeps its own gradient) it is not.  Runs in a child process, which sets the
host device count before it imports jax."""

import os
import subprocess
import sys
import textwrap

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
    cell = tiny_cell("whisper-medium.train.1chip", traffic="hoplite-chain-b8-s448", chips=4)
    r = harness.run(cell, 2**31 + 9, 0.5, False, jax.devices(), time.perf_counter())
    print(json.dumps({{"correct": r["correct"], "checks": r["checks"], "count": r["device"]["count"]}}))
    """
)


def _child(broken: bool) -> dict:
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(here=HERE, broken=broken)],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_chain_synced_run_is_correct():
    r = _child(broken=False)
    assert r["count"] == 4
    assert r["correct"], r["checks"]


def test_run_without_the_exchange_is_not_correct():
    r = _child(broken=True)
    assert not r["correct"], r["checks"]
