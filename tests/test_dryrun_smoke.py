"""Dry-run path regression test: one real production-mesh cell compiles.

Runs the cheapest cell (rwkv6 decode) through the actual
launch/dryrun.py machinery in a subprocess with 512 forced host devices
-- guards the AOT lowering path (shardings, cache skeletons, HLO walker)
against regressions without paying for the full 68-cell sweep.
"""

import subprocess
import sys


def test_one_production_cell_compiles(tmp_path):
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import sys
sys.path.insert(0, "src")
from repro.launch import dryrun
dryrun.ARTIFACT_DIR = {str(tmp_path)!r}  # keep the committed artifact as it is
rec = dryrun.run_cell("rwkv6-1.6b", "decode_32k", "single", "hoplite_chain",
                      force=True)
assert rec["ok"], rec.get("error")
assert rec["walker"]["flops"] > 0
assert rec["memory"]["temp_size_in_bytes"] < 16 * 2**30  # fits v5e
print("cell ok", rec["walker"]["flops"])
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=560
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "cell ok" in proc.stdout
    assert (tmp_path / "single" / "rwkv6-1.6b__decode_32k.json").exists()
