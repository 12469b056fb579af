"""Pallas TPU flash attention (forward) with explicit VMEM BlockSpecs.

Grid: (batch*q_heads, num_q_blocks, num_kv_blocks) -- the kv dim is the
innermost (sequential on TPU), so the streaming-softmax state (m, l, acc)
lives in VMEM scratch across kv steps of one (head, q-block) program.

BlockSpecs move one (block_q, head_dim) query tile and one
(block_kv, head_dim) key/value tile HBM->VMEM per step; GQA is handled in
the k/v index_map (q head h reads kv head h // group).  Causal and
sliding-window masks are applied from global positions; with causal=True
kv blocks entirely above the diagonal still run (masked) -- the
skip-upper-blocks optimization is noted in EXPERIMENTS §Perf.

MXU alignment: block_q/block_kv default 512/512 and head_dim is padded to
a multiple of 128 by ops.py before the call.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, block_q, D)
    k_ref,  # (1, block_kv, D)
    v_ref,  # (1, block_kv, D)
    o_ref,  # (1, block_q, D)
    m_ref,  # scratch (block_q,)
    l_ref,  # scratch (block_q,)
    acc_ref,  # scratch (block_q, D)
    *,
    scale: float,
    causal: bool,
    window: int,
    q_offset: int,
    num_kv_blocks: int,
    block_q: int,
    block_kv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0].astype(jnp.float32)  # (bkv, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bkv)

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o = jnp.where(
            l[:, None] > 0, acc_ref[...] / jnp.maximum(l, 1e-30)[:, None], 0.0
        )
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Kh, Skv, D)
    v: jax.Array,  # (B, Kh, Skv, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Kh, Skv = k.shape[1], k.shape[2]
    G = H // Kh
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    nq, nkv = Sq // block_q, Skv // block_kv

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Kh, Skv, D)
    vf = v.reshape(B * Kh, Skv, D)

    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / math.sqrt(D),
        causal=causal,
        window=window,
        q_offset=q_offset,
        num_kv_blocks=nkv,
        block_q=block_q,
        block_kv=block_kv,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)

