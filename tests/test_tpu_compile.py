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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import collectives as C
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.models.attention import flash_ref

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
