"""Rematerialization per layer (``train/step._loss_with_remat``): a block
of one layer is the same checkpoint as under the block-level remat it
replaced (``remat_helpers.block_remat_loss``), so the one-layer cells
(whisper's, starcoder's) compile to the same step as before.  That a block
of ten layers now keeps one layer's activations in its backward is checked
on the described TPU (``tests/test_tpu_compile.py``): the same step
compiled for the CPU keeps as many bytes live either way."""

import re

import jax
import pytest
from jax.sharding import NamedSharding

from bench_chip_helpers import tiny_cell
from bench_chip_scope_helpers import step_parts
from remat_helpers import block_remat_loss
from repro.data.pipeline import host_batch
from repro.launch.train import build_step
from repro.train import step as TS


def compiled_step(cell, block_remat: bool):
    """``cell``'s train step compiled on the CPU, per-layer or block remat."""
    cfg, shape, mesh, opts = step_parts(cell)
    orig = TS._loss_with_remat
    if block_remat:
        TS._loss_with_remat = block_remat_loss
    try:
        with jax.set_mesh(mesh):
            step, bspecs = build_step(cfg, shape, mesh, opts)
            batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
                     for k, v in host_batch(cfg, shape, 0).items()}
            return step.lower(TS.abstract_state(cfg), batch).compile()
    finally:
        TS._loss_with_remat = orig


def computations(compiled) -> str:
    """The compiled module's computations, without the op metadata and the
    tables of source locations, which name the functions that traced them."""
    text = compiled.as_text()
    return re.sub(r", metadata=\{[^}]*\}", "", text[text.index("\n%"):])


@pytest.mark.parametrize("workload", ["whisper-medium.train.1chip", "starcoder2-3b-l6.train.1chip"])
def test_one_layer_patterns_compile_to_the_same_step(workload):
    per_layer = computations(compiled_step(tiny_cell(workload), block_remat=False))
    block = computations(compiled_step(tiny_cell(workload), block_remat=True))
    assert per_layer == block

