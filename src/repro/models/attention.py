"""Attention: GQA / MHA, full / sliding-window / cross, train + decode.

The training/prefill path uses a blocked streaming-softmax implementation
(pure jnp "flash" algorithm: double lax.scan over query and key blocks,
O(S * block) memory) so that 32k prefill never materializes an S x S score
matrix -- required for the dry-run's memory analysis to be meaningful.

Block contract (`block_plan`, a function of the lengths alone): a length
keeps its largest power-of-two-halving divisor block (from 2048 query /
1024 key positions) where that block is at least 128 or the whole length.
Otherwise the length is padded up to a multiple of a lane-aligned block,
the largest of 1024 / 512 / 256 / 128 that pads at most 1/8 of the padded
length: whisper's 1500 frames run as 1536 in 512-key blocks, not in
375 blocks of 4.  Pad keys are zeros at position -1, which the mask drops;
pad query rows repeat the last position and are sliced off.  The padding
lives inside the forward and backward (named scope ``kv_pad``), so the
custom VJP saves nothing padded.
The Pallas kernel in repro/kernels/flash_attention.py implements the same
contract for the TPU target; kernels/ref.py delegates here.

Decode attends one query position against a (possibly sequence-sharded)
KV cache; softmax reductions over the sharded length partition cleanly
under GSPMD (flash-decoding-style partial-softmax combine).
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import Param, apply_rope, apply_mrope, dense, rmsnorm

NEG_INF = -1e30


def attn_skel(cfg, cross: bool = False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = {
        "wq": Param((d, qd), ("embed", "heads")),
        "wk": Param((d, kvd), ("embed", "kv")),
        "wv": Param((d, kvd), ("embed", "kv")),
        "wo": Param((qd, d), ("heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        s["q_norm"] = Param((cfg.head_dim,), (None,), init="zeros")
        s["k_norm"] = Param((cfg.head_dim,), (None,), init="zeros")
    return s


# ---------------------------------------------------------------------------
# blocked streaming-softmax attention (training / prefill)
# ---------------------------------------------------------------------------


_LANE_BLOCKS = (1024, 512, 256, 128)


def _axis_plan(n: int, cap: int) -> Tuple[int, int]:
    """(block, padded length) for one axis of `block_plan`."""
    b = min(n, cap)
    while n % b:
        b //= 2
    if b >= _LANE_BLOCKS[-1] or b == n:
        return b, n
    for b in _LANE_BLOCKS:
        pad = -n % b
        # n > cap >= 1024 here, so 128 pads under 1/8 and the loop returns
        if pad * 8 <= n + pad or b == _LANE_BLOCKS[-1]:
            return b, n + pad


def block_plan(sq: int, skv: int) -> Tuple[int, int, int, int]:
    """(qb, kb, sq_pad, skv_pad): the blocks of the streaming softmax and the
    lengths they tile; a length without a good divisor block is padded."""
    qb, sq_pad = _axis_plan(sq, 2048)
    kb, skv_pad = _axis_plan(skv, 1024)
    return qb, kb, sq_pad, skv_pad


def _pad_axis(x, n: int, axis: int, **how):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, widths, **how)


def _unpad(x, n: int, axis: int):
    return x if x.shape[axis] == n else lax.slice_in_dim(x, 0, n, axis=axis)


def _pad_lengths(q, k, v, q_pos, kv_pos, sq_pad, skv_pad, rows=()):
    """Pad q and the per-query `rows` with zeros to sq_pad, pad query positions
    with the last one (it has a valid key), and pad k, v with zero keys at
    position -1 (masked) to skv_pad."""
    if sq_pad != q.shape[3]:
        q, *rows = (_pad_axis(x, sq_pad, 3) for x in (q, *rows))
        q_pos = _pad_axis(q_pos, sq_pad, 0, mode="edge")
    if skv_pad != k.shape[2]:
        k, v = _pad_axis(k, skv_pad, 2), _pad_axis(v, skv_pad, 2)
        kv_pos = _pad_axis(kv_pos, skv_pad, 0, constant_values=-1)
    return q, k, v, q_pos, kv_pos, tuple(rows)


def _pad_scope(sq: int, skv: int, sq_pad: int, skv_pad: int):
    padded = (sq_pad, skv_pad) != (sq, skv)
    return jax.named_scope("kv_pad") if padded else contextlib.nullcontext()


def _mask_for(qpos, kpos, causal: bool, window: int):
    mask = kpos[None, :] >= 0
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    return mask  # (qb, kb)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_ref(q, k, v, q_pos, kv_pos, causal, window=0, scale=None):
    """Streaming-softmax attention; returns (B, K, G, Sq, D).  Scores are
    q.k times ``scale`` (None: 1/sqrt(D)).

    custom_vjp: the backward pass recomputes score blocks from (q,k,v,lse)
    instead of saving the per-block probabilities -- without this, autodiff
    of the forward scan stores O(Sq*Skv) f32 residuals and training memory
    explodes (observed 8 GiB/buffer on the 3B train_4k dry-run).  This is
    the exact contract the Pallas kernel implements on TPU.
    """
    out, _lse = _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale)
    return out


def _score_scale(scale, D: int) -> float:
    return 1.0 / math.sqrt(D) if scale is None else scale


def _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale):
    Sq, Skv = q.shape[3], k.shape[2]
    qb, kb, sq_pad, skv_pad = block_plan(Sq, Skv)
    with _pad_scope(Sq, Skv, sq_pad, skv_pad):
        q, k, v, q_pos, kv_pos, _ = _pad_lengths(q, k, v, q_pos, kv_pos, sq_pad, skv_pad)
        out, lse = _flash_fwd_blocks(q, k, v, q_pos, kv_pos, causal, window, qb, kb, scale)
        return _unpad(out, Sq, 3), _unpad(lse, Sq, 3)


def _flash_fwd_blocks(q, k, v, q_pos, kv_pos, causal, window, qb, kb, scale):
    B, K, G, Sq, D = q.shape
    Skv = k.shape[2]
    nq, ns = Sq // qb, Skv // kb
    scale = _score_scale(scale, D)

    qs = q.reshape(B, K, G, nq, qb, D).transpose(3, 0, 1, 2, 4, 5)
    qp = q_pos.reshape(nq, qb)
    ks = k.reshape(B, K, ns, kb, D).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, K, ns, kb, D).transpose(2, 0, 1, 3, 4)
    kp = kv_pos.reshape(ns, kb)

    def q_step(_, qx):
        qblk, qpos = qx  # (B,K,G,qb,D), (qb,)

        def kv_step(carry, kx):
            m, l, acc = carry
            kblk, vblk, kpos = kx
            s = jnp.einsum(
                "bkgqd,bksd->bkgqs", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            mask = _mask_for(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qb), jnp.float32)
        a0 = jnp.zeros((B, K, G, qb, D), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (ks, vs, kp))
        out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # (B,K,G,qb)
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = lax.scan(q_step, None, (qs, qp))  # (nq, ...)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, K, G, Sq, D)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, K, G, Sq)
    return out, lse


def _flash_fwd(q, k, v, q_pos, kv_pos, causal, window, scale):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _flash_bwd(causal, window, scale, res, dout):
    """Blockwise flash backward: recompute p per (q,kv) block pair.

    dv = p^T dout ; dp = dout v^T ; ds = p * (dp - rowsum(dout*out)) ;
    dq = ds k * scale ; dk = ds^T q * scale.
    """
    q, k, v, q_pos, kv_pos, out, lse = res
    Sq, Skv = q.shape[3], k.shape[2]
    qb, kb, sq_pad, skv_pad = block_plan(Sq, Skv)
    with _pad_scope(Sq, Skv, sq_pad, skv_pad):
        delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        # pad rows: zero q and dout give a finite p and zero ds and dv terms
        qp, kp, vp, q_pos, kv_pos, (dout, lse, delta) = _pad_lengths(
            q, k, v, q_pos, kv_pos, sq_pad, skv_pad, (dout, lse, delta)
        )
        dq, dkf, dvf = _flash_bwd_blocks(
            qp, kp, vp, q_pos, kv_pos, dout, lse, delta, causal, window, qb, kb, scale
        )
        return (
            _unpad(dq, Sq, 3).astype(q.dtype),
            _unpad(dkf, Skv, 2).astype(k.dtype),
            _unpad(dvf, Skv, 2).astype(v.dtype),
            None,
            None,
        )


def _flash_bwd_blocks(q, k, v, q_pos, kv_pos, dout, lse, delta, causal, window, qb, kb, scale):
    B, K, G, Sq, D = q.shape
    Skv = k.shape[2]
    nq, ns = Sq // qb, Skv // kb
    scale = _score_scale(scale, D)

    qs = q.reshape(B, K, G, nq, qb, D).transpose(3, 0, 1, 2, 4, 5)
    dos = dout.reshape(B, K, G, nq, qb, D).transpose(3, 0, 1, 2, 4, 5)
    lses = lse.reshape(B, K, G, nq, qb).transpose(3, 0, 1, 2, 4)
    deltas = delta.reshape(B, K, G, nq, qb).transpose(3, 0, 1, 2, 4)
    qp = q_pos.reshape(nq, qb)
    ks = k.reshape(B, K, ns, kb, D).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, K, ns, kb, D).transpose(2, 0, 1, 3, 4)
    kp = kv_pos.reshape(ns, kb)

    kidx = jnp.arange(ns, dtype=jnp.int32) * kb

    def q_step(carry, qx):
        # carry: full dk/dv f32 accumulators (the only O(Skv) buffers);
        # dq blocks stream out as stacked ys -- no O(nq*ns) residuals.
        dkf, dvf = carry
        qblk, doblk, lseblk, delblk, qpos = qx

        def kv_step(c, kx):
            dkf, dvf, dq_acc = c
            kblk, vblk, kpos, koff = kx
            s = jnp.einsum(
                "bkgqd,bksd->bkgqs", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            mask = _mask_for(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseblk[..., None])  # (B,K,G,qb,kb)
            dp = jnp.einsum(
                "bkgqd,bksd->bkgqs", doblk, vblk, preferred_element_type=jnp.float32
            )
            ds = p * (dp - delblk[..., None]) * scale
            dv_b = jnp.einsum(
                "bkgqs,bkgqd->bksd", p.astype(doblk.dtype), doblk,
                preferred_element_type=jnp.float32,
            )
            dk_b = jnp.einsum(
                "bkgqs,bkgqd->bksd", ds.astype(qblk.dtype), qblk,
                preferred_element_type=jnp.float32,
            )
            dq_acc = dq_acc + jnp.einsum(
                "bkgqs,bksd->bkgqd", ds.astype(kblk.dtype), kblk,
                preferred_element_type=jnp.float32,
            )
            cur_k = lax.dynamic_slice_in_dim(dkf, koff, kb, axis=2)
            dkf = lax.dynamic_update_slice_in_dim(dkf, cur_k + dk_b, koff, axis=2)
            cur_v = lax.dynamic_slice_in_dim(dvf, koff, kb, axis=2)
            dvf = lax.dynamic_update_slice_in_dim(dvf, cur_v + dv_b, koff, axis=2)
            return (dkf, dvf, dq_acc), None

        dq0 = jnp.zeros((B, K, G, qb, D), jnp.float32)
        (dkf, dvf, dq_b), _ = lax.scan(kv_step, (dkf, dvf, dq0), (ks, vs, kp, kidx))
        return (dkf, dvf), dq_b

    dk0 = jnp.zeros((B, K, Skv, D), jnp.float32)
    dv0 = jnp.zeros((B, K, Skv, D), jnp.float32)
    (dkf, dvf), dq_blocks = lax.scan(
        q_step, (dk0, dv0), (qs, dos, lses, deltas, qp)
    )
    dq = dq_blocks.transpose(1, 2, 3, 0, 4, 5).reshape(B, K, G, Sq, D)
    return dq, dkf, dvf


flash_ref.defvjp(_flash_fwd, _flash_bwd)


def decode_attend(
    q: jax.Array,  # (B, K, G, 1, D)
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,  # (B, S, K, D)
    kv_positions: jax.Array,  # (S,) true token position per slot; < 0 invalid
    t: jax.Array,  # scalar: current position
    window: int = 0,
    scale: Optional[float] = None,  # None: 1/sqrt(D)
) -> jax.Array:
    """One-token attention over the cache.  Under GSPMD the length
    reductions become partial-softmax combines across cache shards."""
    scale = _score_scale(scale, q.shape[-1])
    s = jnp.einsum(
        "bkgqd,bskd->bkgqs", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    mask = (kv_positions >= 0) & (kv_positions <= t)
    if window:
        mask &= (t - kv_positions) < window
    s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# full layer
# ---------------------------------------------------------------------------


def _split_heads(cfg, xq, xk, xv):
    B, S = xq.shape[:2]
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    q = xq.reshape(B, S, K, G, D)
    k = xk.reshape(B, S, K, D)
    v = xv.reshape(B, S, K, D)
    return q, k, v


def _positions_rope(cfg, p, q, k, q_pos, kv_pos, positions_3d=None):
    """Apply qk-norm then rotary embedding.  q: (B,S,K,G,D), k: (B,S,K,D)."""
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.rope == "rope":
        B, S = q.shape[:2]
        qf = q.reshape(B, S, -1, cfg.head_dim)
        qf = apply_rope(qf, q_pos[None, :], cfg.rope_theta)
        q = qf.reshape(q.shape)
        k = apply_rope(k, kv_pos[None, :], cfg.rope_theta)
    elif cfg.rope == "mrope":
        B, S = q.shape[:2]
        if positions_3d is None:
            positions_3d = jnp.broadcast_to(q_pos[None, None, :], (3, B, S))
        qf = q.reshape(B, S, -1, cfg.head_dim)
        qf = apply_mrope(qf, positions_3d, cfg.rope_theta, cfg.mrope_sections)
        q = qf.reshape(q.shape)
        k = apply_mrope(k, positions_3d, cfg.rope_theta, cfg.mrope_sections)
    return q, k


@jax.named_scope("attention")
def attention_fwd(
    cfg,
    p,
    x: jax.Array,  # (B, S, d)
    spec,  # LayerSpec
    q_pos: jax.Array,  # (S,)
    positions_3d=None,
    kv_x: Optional[jax.Array] = None,  # cross-attention source
    kv_pos: Optional[jax.Array] = None,
    causal: bool = True,
) -> jax.Array:
    """Training/prefill attention (no cache)."""
    cross = kv_x is not None
    src = kv_x if cross else x
    xq = dense(x, p["wq"])
    xk = dense(src, p["wk"])
    xv = dense(src, p["wv"])
    B, Sq = x.shape[:2]
    Skv = src.shape[1]
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    q = xq.reshape(B, Sq, K, G, D)
    k = xk.reshape(B, Skv, K, D)
    v = xv.reshape(B, Skv, K, D)
    if kv_pos is None:
        kv_pos = q_pos if not cross else jnp.arange(Skv)
    if not cross:
        q, k = _positions_rope(cfg, p, q, k, q_pos, kv_pos, positions_3d)
    qh = q.transpose(0, 2, 3, 1, 4)  # (B,K,G,Sq,D)
    kh = k.transpose(0, 2, 1, 3)  # (B,K,Skv,D)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_ref(
        qh, kh, vh, q_pos, kv_pos,
        causal=causal and not cross,
        window=spec.window if spec.attention == "window" else 0,
        scale=cfg.attention_scale,
    )
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H * D)
    return dense(out, p["wo"])


def attention_prefill_kv(cfg, p, x, q_pos, positions_3d=None):
    """Compute the K/V tensors to seed a decode cache: (B,S,K,D) pair."""
    xk = dense(x, p["wk"])
    xv = dense(x, p["wv"])
    B, S = x.shape[:2]
    K, D = cfg.num_kv_heads, cfg.head_dim
    k = xk.reshape(B, S, K, D)
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"])
    if cfg.rope == "rope":
        k = apply_rope(k, q_pos[None, :], cfg.rope_theta)
    elif cfg.rope == "mrope":
        if positions_3d is None:
            positions_3d = jnp.broadcast_to(q_pos[None, None, :], (3, B, S))
        k = apply_mrope(k, positions_3d, cfg.rope_theta, cfg.mrope_sections)
    return k, xv.reshape(B, S, K, D)


def attention_decode(
    cfg,
    p,
    x: jax.Array,  # (B, 1, d)
    spec,
    cache: Tuple[jax.Array, jax.Array],  # k,v: (B, C, K, D); C = S or window
    t: jax.Array,  # scalar position of the new token
    cross: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step: returns (output, updated cache).

    Windowed layers use a RING cache of length `window`: slot j holds the
    most recent position congruent to j (mod W) -- this is what bounds the
    KV footprint for SWA/local layers at 500k context."""
    B = x.shape[0]
    K, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    G = H // K
    xq = dense(x, p["wq"])
    q = xq.reshape(B, 1, K, G, D)
    k_cache, v_cache = cache
    C = k_cache.shape[1]
    if cross:
        # cross-attention cache is static (encoder output); no update; all
        # slots valid (their positions are 0..C-1, always <= t)
        qh = q.transpose(0, 2, 3, 1, 4)
        kv_positions = jnp.arange(C, dtype=jnp.int32)
        out = decode_attend(qh, k_cache, v_cache, kv_positions, jnp.int32(C - 1),
                            scale=cfg.attention_scale)
    else:
        xk = dense(x, p["wk"]).reshape(B, 1, K, D)
        xv = dense(x, p["wv"]).reshape(B, 1, K, D)
        pos = jnp.full((1,), t, jnp.int32)
        q, xk = _positions_rope(cfg, p, q, xk, pos, pos)
        windowed = spec.attention == "window" and C == spec.window
        slot = (t % C) if windowed else t
        k_cache = lax.dynamic_update_slice_in_dim(k_cache, xk.astype(k_cache.dtype), slot, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(v_cache, xv.astype(v_cache.dtype), slot, axis=1)
        j = jnp.arange(C, dtype=jnp.int32)
        if windowed:
            kv_positions = t - ((t - j) % C)  # ring: in (t-C, t]; <0 => empty
        else:
            kv_positions = j  # linear cache: slot == position
        qh = q.transpose(0, 2, 3, 1, 4)
        out = decode_attend(
            qh, k_cache, v_cache, kv_positions, t,
            window=spec.window if spec.attention == "window" else 0,
            scale=cfg.attention_scale,
        )
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H * D)
    return dense(out, p["wo"]), (k_cache, v_cache)
