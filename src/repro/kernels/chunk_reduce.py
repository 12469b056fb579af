"""Pallas chunked-accumulate kernels -- the Hoplite Reduce hot op.

Every hop of a Hoplite reduce chain computes ``out = dst + alpha*src``
over a streamed chunk (paper section 4.3: "It computes the intermediate
object by reducing the input object in its local store with the pushed
object"); on TPU this is the per-chunk body of core/collectives.py's
chain schedules.  ``dequant_add`` is the compressed-chain variant
(int8 payload + per-block scales, matching optim/compression.py).

BlockSpec tiling: ``chunk_reduce`` streams 1-D tiles of ``block``
elements through VMEM; ``dequant_add`` streams (rows, qblock) tiles.
Accumulation is in f32 regardless of storage dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _acc_kernel(dst_ref, src_ref, o_ref, *, alpha: float):
    d = dst_ref[...].astype(jnp.float32)
    s = src_ref[...].astype(jnp.float32)
    o_ref[...] = (d + alpha * s).astype(o_ref.dtype)


def chunk_reduce(
    dst: jax.Array,
    src: jax.Array,
    alpha: float = 1.0,
    block: int = 16 * 1024,
    interpret: bool = False,
) -> jax.Array:
    """out = dst + alpha * src, tiled through VMEM. Shapes must match."""
    assert dst.shape == src.shape
    flat_d = dst.reshape(-1)
    flat_s = src.reshape(-1)
    n = flat_d.shape[0]
    block = min(block, n)
    pad = (-n) % block
    if pad:
        flat_d = jnp.pad(flat_d, (0, pad))
        flat_s = jnp.pad(flat_s, (0, pad))
    grid = (flat_d.shape[0] // block,)
    out = pl.pallas_call(
        functools.partial(_acc_kernel, alpha=alpha),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(flat_d.shape, dst.dtype),
        interpret=interpret,
    )(flat_d, flat_s)
    return out[:n].reshape(dst.shape)


def _dequant_add_kernel(dst_ref, q_ref, scale_ref, o_ref):
    d = dst_ref[...].astype(jnp.float32)  # (rows, qblock)
    q = q_ref[...].astype(jnp.float32)  # (rows, qblock)
    o_ref[...] = (d + q * scale_ref[...]).astype(o_ref.dtype)  # scale (rows, 1)


def dequant_add(
    dst: jax.Array,
    q: jax.Array,  # int8, padded to multiple of qblock
    scale: jax.Array,  # f32 per-qblock scales
    qblock: int = 256,
    block_rows: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """dst + dequant(q, scale): the compressed chain-hop accumulate.

    Laid out 2-D as (quant blocks, qblock) with the scales as a
    (quant blocks, 1) column, so every tile is lane-aligned on TPU (a 1-D
    scale tile of block // qblock elements is not)."""
    flat_d = dst.reshape(-1)
    n = flat_d.shape[0]
    npad = q.size  # already padded to qblock multiple
    assert npad % qblock == 0 and npad >= n
    rows = npad // qblock
    block_rows = min(block_rows, rows)
    pad_rows = (-rows) % block_rows
    total = rows + pad_rows
    df = jnp.pad(flat_d, (0, total * qblock - n)).reshape(total, qblock)
    qf = jnp.pad(q.reshape(rows, qblock), ((0, pad_rows), (0, 0)))
    sf = jnp.pad(scale.reshape(rows, 1), ((0, pad_rows), (0, 0)))
    out = pl.pallas_call(
        _dequant_add_kernel,
        grid=(total // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, qblock), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, qblock), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, qblock), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(df.shape, dst.dtype),
        interpret=interpret,
    )(df, qf, sf)
    return out.reshape(-1)[:n].reshape(dst.shape)
