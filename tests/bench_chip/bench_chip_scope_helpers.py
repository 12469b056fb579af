"""Helpers of the scope self-tests: the parts of a cell's train step, and
the ``op_name`` of every op of it compiled, on the host CPU."""

from __future__ import annotations

from bench_chip_helpers import harness


def step_parts(cell: harness.Cell):
    """(ModelConfig, ShapeSpec, mesh, TrainOptions) of ``cell``'s train
    step, as ``harness.run`` builds them, over the first ``cell.chips``
    devices jax has."""
    import jax

    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.train import step as TS

    t = cell.traffic
    cfg = harness.model_config(cell.config["program"])
    shape = ShapeSpec(cell.name, t["seq_len"], t["global_batch"], "train")
    mesh = make_mesh(jax.devices()[: cell.chips], pod=t["pod_sync"] != "gspmd")
    opts = TS.TrainOptions(num_microbatches=t["microbatches"], remat=t["remat"],
                           pod_sync=t["pod_sync"], adamw=AdamWConfig(**t["adamw"]))
    return cfg, shape, mesh, opts


def compiled_op_names(cell: harness.Cell) -> list:
    """The ``op_name`` of every op in ``cell``'s train step, compiled."""
    import re

    import jax
    from jax.sharding import NamedSharding

    from repro.data.pipeline import host_batch
    from repro.launch.train import build_step
    from repro.train import step as TS

    cfg, shape, mesh, opts = step_parts(cell)
    with jax.set_mesh(mesh):
        step, bspecs = build_step(cfg, shape, mesh, opts)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, bspecs[k]))
                 for k, v in host_batch(cfg, shape, 0).items()}
        text = step.lower(TS.abstract_state(cfg), batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)
