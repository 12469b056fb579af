"""Shared helpers of the chip benchmark's CPU self-tests: tiny cells of the
benchmark's configurations, run on the host CPU."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

TINY = dict(num_layers=2, d_model=64, num_heads=4, d_ff=128, vocab_size=509, head_dim=16,
            dtype="float32", param_dtype="float32")


def tiny_cell(workload: str, seq: int = 32, batch: int = 8, traffic: str = None,
              chips: int = None) -> harness.Cell:
    """``workload`` with every width cut to a CPU test's size and f32
    weights; the limits stay the cell's own, and so do the traffic
    settings and chips unless ``traffic`` (a file of ``traffic/``) and
    ``chips`` are given."""
    cell = harness.load_cell(workload)
    if traffic:
        cell.traffic = harness._json("traffic", traffic + ".json")
    cell.chips = chips or cell.chips
    program = dict(cell.config["program"], **TINY)
    program["num_kv_heads"] = 4 if program["num_kv_heads"] == program["num_heads"] else 2
    program["num_heads"] = 4
    if program.get("encoder_layers"):
        program.update(encoder_layers=2, encoder_seq=32)
    cell.config = dict(cell.config, program=program)
    cell.traffic = dict(cell.traffic, seq_len=seq, global_batch=batch)
    return cell
