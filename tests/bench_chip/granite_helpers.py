"""The granite cell at a CPU test's size: ``bench_chip_helpers.tiny_cell``'s
widths, and the Mamba-2 mixers cut to match (4 heads of 32 for d_model 64
at expand 2, state 8, chunk 12), with the whole 10-layer pattern."""

from __future__ import annotations

from bench_chip_helpers import harness, tiny_cell

WORKLOAD = "granite-4.0-h-micro-l10.train.1chip"
TINY_MAMBA = dict(num_layers=10, ssm_heads=4, ssm_head_dim=32, ssm_state_dim=8, ssm_chunk=12)


def tiny_granite(seq: int = 30, batch: int = 2) -> harness.Cell:
    """The cell at CPU size; 30 positions are 2.5 chunks of 12."""
    cell = tiny_cell(WORKLOAD, seq=seq, batch=batch)
    cell.config = dict(cell.config, program=dict(cell.config["program"], **TINY_MAMBA))
    return cell
