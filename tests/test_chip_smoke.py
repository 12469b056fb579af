"""``chip_smoke.py`` off the chip: it refuses to report a CPU run, and its
phases pass on host devices with the reduced whisper config (the chip run
uses the full-width one)."""

import os
import subprocess
import sys
import textwrap

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced_config  # noqa: E402
from repro.configs.base import ShapeSpec  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

SMALL = ShapeSpec("t", 32, 8, "train")


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=120, cwd=ROOT, env=env,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_train_phase_lowers_the_loss():
    cfg = reduced_config(get_config(chip_smoke.ARCH))
    r = chip_smoke.train_run(cfg, SMALL, make_mesh(jax.devices()[:1]),
                             chip_smoke.smoke_options(chip_smoke.TRAIN_STEPS),
                             steps=chip_smoke.TRAIN_STEPS)
    chip_smoke.check_training(r["losses"])
    assert r["compile_s"] > 0 and r["step_s"] > 0


def test_four_device_phases_agree_with_psum():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, ".")
        import jax
        import chip_smoke as cs
        from repro.configs import get_config, reduced_config
        from repro.configs.base import ShapeSpec

        rows = cs.check_allreduces(jax.devices(), bucket_bytes=(4 << 10, 1 << 20), reps=1)
        assert len(rows) == 6 and all(r["allclose"] for r in rows)
        cfg = reduced_config(get_config(cs.ARCH))
        runs = cs.compare_pod_syncs(cfg, ShapeSpec("t", 32, 8, "train"), jax.devices(), 3)
        assert all(len(runs[s]["losses"]) == 3 for s in cs.POD_SYNCS)
        print("four-device phases ok")
        """
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600,
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "four-device phases ok" in proc.stdout
