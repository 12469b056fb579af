"""jit'd public wrappers for the Pallas kernels.

``flash_attention`` carries a custom_vjp whose backward is the blockwise
jnp formulation from models/attention.py -- the forward runs the Pallas
kernel, the backward the XLA-fused ref.  Every wrapper compiles for the
TPU unless the caller passes ``interpret=True`` (the CPU tests do); there
is no automatic switch, so a kernel never silently runs in the
interpreter on a device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import chunk_reduce as _cr
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0, interpret=False):
    """(B,H,Sq,D) x (B,Kh,Skv,D)^2 -> (B,H,Sq,D); GQA via H//Kh groups."""
    return _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        interpret=interpret,
    )


def _fa_fwd(q, k, v, causal, window, q_offset, interpret):
    out = flash_attention(q, k, v, causal, window, q_offset, interpret)
    return out, (q, k, v, out)


def _fa_bwd(causal, window, q_offset, interpret, res, dout):
    """Blockwise recompute backward via the models/attention ref math."""
    from repro.models.attention import flash_ref

    q, k, v, out = res
    B, H, Sq, D = q.shape
    Kh, Skv = k.shape[1], k.shape[2]
    G = H // Kh
    qr = q.reshape(B, Kh, G, Sq, D)
    q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    kv_pos = jnp.arange(Skv, dtype=jnp.int32)

    def f(qr_, k_, v_):
        return flash_ref(qr_, k_, v_, q_pos, kv_pos, causal, window)

    _, vjp = jax.vjp(f, qr, k, v)
    dq, dk, dv = vjp(dout.reshape(B, Kh, G, Sq, D))
    return dq.reshape(B, H, Sq, D), dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def chunk_reduce(dst, src, alpha: float = 1.0, block: int = 16 * 1024, interpret: bool = False):
    return _cr.chunk_reduce(dst, src, alpha=alpha, block=block, interpret=interpret)


def dequant_add(dst, q, scale, qblock: int = 256, interpret: bool = False):
    return _cr.dequant_add(dst, q, scale, qblock=qblock, interpret=interpret)


def rmsnorm(x, w, eps: float = 1e-6, interpret: bool = False):
    return _rn.rmsnorm(x, w, eps=eps, interpret=interpret)
