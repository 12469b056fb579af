"""Launch layer on the devices that exist: mesh layouts, compile-cache
placement, the prefetcher's error path and the train driver end to end."""

import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import ARCHS, reduced_config
from repro.configs.base import ShapeSpec
from repro.data import pipeline
from repro.launch.mesh import make_mesh
from repro.sharding import partitioning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, timeout=300):
    """A CPU subprocess from the repo root; ``env`` is all it inherits of
    XLA_FLAGS and JAX_COMPILATION_CACHE_DIR."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env = dict(base, PYTHONPATH="src", JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=timeout,
        cwd=ROOT, env=env,
    )


def test_make_mesh_one_device_is_data_model_auto():
    mesh = make_mesh()
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert set(mesh.axis_types) == {AxisType.Auto}


@pytest.mark.parametrize("kwargs", [{"pod": True}, {"model": 2}, {"model": 0}])
def test_make_mesh_refuses_layouts_the_devices_do_not_fill(kwargs):
    with pytest.raises(ValueError):
        make_mesh(jax.devices()[:1], **kwargs)


def test_make_mesh_four_devices():
    code = (
        "import jax; from repro.launch.mesh import make_mesh\n"
        "d = jax.devices()\n"
        "print(dict(make_mesh(d).shape), dict(make_mesh(d, pod=True).shape),"
        " dict(make_mesh(d, model=2).shape))\n"
    )
    proc = _run(["-c", code], {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == (
        "{'data': 4, 'model': 1} {'pod': 4, 'data': 1, 'model': 1} {'data': 2, 'model': 2}"
    )


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    code = (
        "import jax; from repro.launch.cache import enable_compile_cache\n"
        "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    proc = _run(["-c", code], env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    want = str(tmp_path) if env_dir else os.path.join(ROOT, ".jax_cache")
    assert proc.stdout.split() == [want, want]


def test_prefetcher_reraises_producer_error():
    cfg = reduced_config(ARCHS["stablelm-3b"])
    shape = ShapeSpec("t", 16, 2, "train")
    mesh = make_mesh()
    specs = partitioning.batch_specs(cfg, mesh, shape)
    del specs["labels"]  # the producer's device_batch raises KeyError
    feed = pipeline.Prefetcher(cfg, shape, mesh, specs)
    try:
        for _ in range(2):  # every later call raises too, none blocks
            with pytest.raises(KeyError):
                next(feed)
    finally:
        feed.close()


@pytest.mark.parametrize("pod_sync", ["gspmd", "hoplite_chain"])
def test_train_driver_builds_mesh_from_devices(tmp_path, pod_sync):
    proc = _run([
        "-m", "repro.launch.train", "--arch", "whisper-medium", "--reduced",
        "--steps", "2", "--seq-len", "16", "--global-batch", "2", "--log-every", "1",
        "--pod-sync", pod_sync,
    ], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    if pod_sync == "gspmd":
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "done: 2 steps" in proc.stdout
    else:  # one CPU device cannot hold a pod axis: refused, never padded
        assert proc.returncode != 0
        assert "pod axis needs at least 2" in proc.stderr


def test_train_driver_marks_steps_and_batches_in_the_profiler_trace(tmp_path):
    code = (
        "import sys, jax; from repro.launch import train\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "train.main(['--arch', 'whisper-medium', '--reduced', '--steps', '2', '--seq-len', '16',"
        " '--global-batch', '2', '--log-every', '1'])\n"
        "jax.profiler.stop_trace()\n"
    )
    proc = _run(["-c", code, str(tmp_path / "trace")], {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-3000:]
    from jax._src.profiler import ProfileData

    (path,) = (tmp_path / "trace").rglob("*.xplane.pb")
    (host,) = [p for p in ProfileData.from_file(str(path)).planes if p.name == "/host:CPU"]
    events = [e for line in host.lines for e in line.events]
    steps = sorted(dict(e.stats)["step_num"] for e in events if e.name == "train")
    assert steps == [0, 1]
    assert sum(e.name == "data/produce" for e in events) >= 2
