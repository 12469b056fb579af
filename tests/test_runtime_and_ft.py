"""Task runtime: futures, dynamic groups, lineage reconstruction; plus
checkpoint/restart + elastic remesh fault-tolerance tests."""

import os
import shutil
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core.api import SUM
from repro.runtime import Runtime, TaskError


def test_remote_and_get():
    rt = Runtime(num_nodes=3)
    r = rt.remote(lambda a, b: a + b, np.arange(4.0), np.ones(4))
    np.testing.assert_array_equal(rt.get(r), np.arange(4.0) + 1)


def test_object_ref_args_resolve_via_store():
    rt = Runtime(num_nodes=3)
    a = rt.put(np.arange(1000.0))
    b = rt.remote(lambda x: x * 2, a, node=1)
    c = rt.remote(lambda x: x.sum(), b, node=2)
    assert float(rt.get(c)) == np.arange(1000.0).sum() * 2


def test_wait_first_k():
    rt = Runtime(num_nodes=2, executors_per_node=8)

    def slow(t):
        time.sleep(float(t))
        return np.float64(t)

    refs = [rt.remote(slow, 0.4), rt.remote(slow, 0.01), rt.remote(slow, 0.02)]
    done, rest = rt.wait(refs, num_returns=2, timeout=10)
    assert len(done) == 2 and len(rest) == 1
    vals = sorted(float(rt.get(d)) for d in done)
    assert vals == [0.01, 0.02]


def test_dynamic_reduce_matches_sum():
    rt = Runtime(num_nodes=4)
    refs = [rt.put(np.full(500, float(i))) for i in range(7)]
    out = rt.reduce(refs, SUM)
    np.testing.assert_allclose(rt.get(out), np.full(500, float(sum(range(7)))))


def test_task_error_propagates():
    rt = Runtime(num_nodes=2)

    def boom():
        raise RuntimeError("boom")

    r = rt.remote(boom)
    with pytest.raises(TaskError):
        rt.get(r)


def test_lineage_reconstruction_after_node_loss():
    rt = Runtime(num_nodes=3)
    r = rt.remote(lambda: np.arange(50_000, dtype=np.float64), node=1)
    rt.get(r, node=1)
    rt.cluster.fail_node(1)
    out = rt.get(r, node=0)
    np.testing.assert_array_equal(out, np.arange(50_000, dtype=np.float64))
    assert rt.tasks_reexecuted == 1


# ---------------------------------------------------------------------------
# checkpoint / restart / elastic (subprocess: needs >1 device for remesh)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_gc(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    for step in (10, 20, 30):
        ck.save(step, jax.tree_util.tree_map(lambda x: x * step, tree))
    assert ck.list_steps() == [20, 30]  # keep=2 gc'd step 10
    step, restored = ck.restore(tree)
    assert step == 30
    np.testing.assert_allclose(np.asarray(restored["a"]), np.arange(10.0) * 30)


def test_checkpoint_async_and_atomic(tmp_path):
    import jax.numpy as jnp

    from repro.checkpoint.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path))
    ck.save_async(5, {"w": jnp.ones(100)})
    ck.wait()
    assert ck.latest_step() == 5
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_elastic_remesh_restore():
    """Checkpoint written on a (4,2) mesh restores onto (2,2) -- elastic
    rescale via the host-numpy interchange format."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, tempfile
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.checkpoint import Checkpointer
        from repro.configs import ARCHS, reduced_config
        from repro.launch.mesh import make_mesh
        from repro.train import step as TS

        cfg = reduced_config(ARCHS["stablelm-3b"])
        d = tempfile.mkdtemp()
        mesh1 = make_mesh(jax.devices(), model=2)  # (4, 2)
        with jax.set_mesh(mesh1):
            st = TS.init_state(cfg, jax.random.PRNGKey(0), mesh1)
            Checkpointer(d).save(7, st)
        mesh2 = make_mesh(jax.devices()[:4], model=2)  # ELASTIC: (2, 2), fewer devices
        with jax.set_mesh(mesh2):
            sh2 = TS.state_shardings(cfg, mesh2)
            step, st2 = Checkpointer(d).restore(TS.abstract_state(cfg), shardings=sh2)
        assert step == 7
        a = jax.tree_util.tree_leaves(st["params"])[0]
        b = jax.tree_util.tree_leaves(st2["params"])[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("elastic ok")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "elastic ok" in proc.stdout


def test_data_pipeline_determinism_across_restart():
    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import host_batch

    cfg = reduced_config(ARCHS["qwen3-14b"])
    shape = ShapeSpec("t", 32, 4, "train")
    a = host_batch(cfg, shape, step=17, seed=3)
    b = host_batch(cfg, shape, step=17, seed=3)  # "restarted" process
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = host_batch(cfg, shape, step=18, seed=3)
    assert not np.array_equal(a["tokens"], c["tokens"])
