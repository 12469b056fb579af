"""Compile the device path for a described (not attached) TPU v5e 2x2.

The installed TPU compiler refuses what the Pallas interpreter accepts:
tiles not aligned to the chip's (8, 128) layout, too much VMEM, programs
that do not fit.  Each test compiles one kernel at a real width, or one
Hoplite allreduce on a 4-chip mesh, and checks the compiled program.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import collectives as C
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.models.attention import flash_ref
from repro.models.ssm import ssd

BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_chip")

MIB = 1 << 20
GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache the caller configured
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_reduce_compiles(one_chip, dtype):
    n = 64 * MIB // jnp.dtype(dtype).itemsize
    x = _spec((n,), dtype, one_chip)
    assert "tpu_custom_call" in _hlo(lambda d, s: ops.chunk_reduce(d, s, alpha=0.5), x, x)


def test_dequant_add_compiles(one_chip):
    n = 64 * MIB // 4
    hlo = _hlo(
        ops.dequant_add,
        _spec((n,), jnp.float32, one_chip),
        _spec((n,), jnp.int8, one_chip),
        _spec((n // 256,), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in hlo


def test_flash_attention_fwd_compiles(one_chip):
    q = _spec((8, 16, 512, 64), jnp.bfloat16, one_chip)  # whisper-medium heads
    assert "tpu_custom_call" in _hlo(ops.flash_attention, q, q, q)


@pytest.mark.parametrize("sq", [1500, 448])
def test_flash_ref_fwd_bwd_over_whisper_frames_compiles(one_chip, sq):
    """whisper-medium's encoder self- and decoder cross-attention over 1500
    frames, forward and backward, in blocks small enough to leave the train
    step's 7.9 GiB of state its room."""
    q = _spec((8, 16, 1, sq, 64), jnp.bfloat16, one_chip)
    kv = _spec((8, 16, 1500, 64), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        q_pos, kv_pos = jnp.arange(sq), jnp.arange(1500)
        out, vjp = jax.vjp(lambda *a: flash_ref(*a, q_pos, kv_pos, False), q, k, v)
        return out, vjp(out)

    compiled = jax.jit(fwd_bwd).lower(q, kv, kv).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * GIB


def test_rmsnorm_compiles(one_chip):
    x = _spec((4096, 1024), jnp.bfloat16, one_chip)
    w = _spec((1024,), jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in _hlo(ops.rmsnorm, x, w)


@pytest.mark.parametrize("allreduce", [C.chain_allreduce, C.two_level_allreduce])
def test_hoplite_allreduce_compiles_on_four_chips(topo, allreduce):
    mesh = auto_mesh((4,), ("x",), topo.devices)
    x = _spec((4, 8 * MIB // 4), jnp.float32, NamedSharding(mesh, P("x")))
    f = jax.shard_map(
        lambda a: allreduce(a, "x"), mesh=mesh, in_specs=P("x"), out_specs=P("x")
    )
    assert "collective-permute" in _hlo(f, x)


def test_ssd_fwd_bwd_at_granite_width_compiles(one_chip):
    """Granite 4.0-H's SSD on one row of 4096 positions: 64 heads of 64,
    state 128, one group, chunks of 256; bf16 inputs, f32 decays."""
    b, s, h, p, g, n = 1, 4096, 64, 64, 1, 128
    x = _spec((b, s, h, p), jnp.bfloat16, one_chip)
    dt = _spec((b, s, h), jnp.float32, one_chip)
    A = _spec((h,), jnp.float32, one_chip)
    B = _spec((b, s, g, n), jnp.bfloat16, one_chip)

    def fwd_bwd(x, dt, A, B, C):
        y, vjp = jax.vjp(lambda *a: ssd(*a, chunk=256)[0], x, dt, A, B, C)
        return y, vjp(y)

    compiled = jax.jit(fwd_bwd).lower(x, dt, A, B, B).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * GIB


def test_ten_layer_pattern_keeps_one_layers_activations(topo):
    """The tiny granite step (a pattern of ten layers in one block) with each
    layer its own checkpoint keeps under half the bytes the block-level
    checkpoint kept live."""
    from jax.sharding import NamedSharding

    sys.path.insert(0, BENCH_TESTS)
    from granite_helpers import tiny_granite
    from remat_helpers import block_remat_loss

    from benchmarks.chip import harness
    from repro.configs.base import ShapeSpec
    from repro.data.pipeline import host_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_step
    from repro.train import step as TS

    cell = tiny_granite(seq=240, batch=4)
    cfg = harness.model_config(cell.config["program"])
    shape = ShapeSpec(cell.name, 240, 4, "train")
    mesh = make_mesh(np.array(topo.devices[:1]))
    opts = TS.TrainOptions(remat="full", pod_sync="gspmd")
    temp = {}
    for block in (False, True):
        orig = TS._loss_with_remat
        if block:
            TS._loss_with_remat = block_remat_loss
        try:
            with jax.set_mesh(mesh):
                step, bspecs = build_step(cfg, shape, mesh, opts)
                state = jax.tree.map(lambda a, sh: _spec(a.shape, a.dtype, sh),
                                     TS.abstract_state(cfg), TS.state_shardings(cfg, mesh, opts))
                batch = {k: _spec(v.shape, v.dtype, NamedSharding(mesh, bspecs[k]))
                         for k, v in host_batch(cfg, shape, 0).items()}
                temp[block] = step.lower(state, batch).compile().memory_analysis().temp_size_in_bytes
        finally:
            TS._loss_with_remat = orig
    assert temp[False] < 0.5 * temp[True], temp
