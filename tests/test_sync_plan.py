"""The gradient sync's chunk plan (core/planner.py) for the four-chip chain
cell, and the planner's independence from JAX."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.planner import POD_CONFIG
from repro.models import transformer as T
from repro.models.common import abstract_params

POD = 4  # ranks on the pod axis of a 2x2 host


def test_whisper_medium_chain_plan_on_the_pod_axis():
    """Every whisper-medium gradient leaf, at the param dtype, in the chunks
    the trainer's pod-axis config plans on 4 ranks: an FFN leaf takes 47, and
    the fused chains run C + 2n - 3 steps each, two ppermutes a step."""
    cfg = get_config("whisper-medium")
    grads = abstract_params(T.model_skel(cfg), jnp.dtype(cfg.param_dtype))
    chunks = {
        jax.tree_util.keystr(path): POD_CONFIG.chunks_for(POD, g.size * g.dtype.itemsize)
        for path, g in jax.tree_util.tree_leaves_with_path(grads)
    }
    assert len(chunks) == 32
    assert chunks["['stages'][0]['pos0']['ffn']['wi']"] == 47
    assert chunks["['encoder']['stage']['pos0']['ffn']['wo']"] == 47
    assert sum(c + 2 * POD - 3 for c in chunks.values()) == 706


def test_planner_and_local_cluster_run_without_jax():
    """With ``import jax`` refused, the planner and the host data plane
    still import, and LocalCluster still autotunes its chunk sizes."""
    code = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, "src")

        class NoJax:
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    raise ImportError(f"{name} is blocked")

        sys.meta_path.insert(0, NoJax())
        from repro.core import planner
        from repro.core.local import LocalCluster

        cluster = LocalCluster(8)
        assert cluster.chunk_size_for(4 << 20) > cluster.chunk_size_for(64 << 10)
        assert not [m for m in sys.modules if m.split(".")[0] in ("jax", "jaxlib")]
        assert "repro.core.collectives" not in sys.modules
        print("ok")
        """
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120, cwd=".")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "ok"
