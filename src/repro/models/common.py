"""Parameter skeleton system + shared layer math.

Models are defined as *skeletons*: nested dicts of ``Param`` descriptors
(shape, dtype, logical axes, initializer).  From one skeleton we derive:

  * concrete initialized params      (smoke tests, examples, real training)
  * ShapeDtypeStruct abstract params (multi-pod dry-run -- no allocation)
  * PartitionSpec trees              (via sharding/partitioning.py rules)

Logical axis names used throughout:
  "layers"  -- scanned block stack dim (never sharded)
  "embed"   -- d_model dim            (FSDP -> data axis)
  "heads"   -- flattened q heads*dim  (TP -> model axis)
  "kv"      -- flattened kv heads*dim (TP -> model axis when divisible)
  "mlp"     -- d_ff dim               (TP -> model axis)
  "vocab"   -- padded vocab dim       (TP -> model axis)
  "expert"  -- MoE expert dim         (EP -> model axis when divisible)
  "ssm"     -- mamba inner dim        (TP -> model axis)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | small
    scale: float = 1.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_param(x) -> bool:
    return isinstance(x, Param)


def tree_map_params(fn, skel):
    return jax.tree_util.tree_map(fn, skel, is_leaf=is_param)


def init_params(skel, key, dtype_override=None):
    """Concrete initialization (host-side, used at small scale)."""
    leaves, treedef = jax.tree_util.tree_flatten(skel, is_leaf=is_param)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, p in zip(keys, leaves):
        dtype = dtype_override or p.dtype
        if p.init == "zeros":
            v = jnp.zeros(p.shape, dtype)
        elif p.init == "ones":
            v = jnp.ones(p.shape, dtype)
        else:
            fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
            std = p.scale / math.sqrt(max(1, fan_in))
            v = (jax.random.normal(k, p.shape, jnp.float32) * std).astype(dtype)
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_params(skel, dtype_override=None):
    """ShapeDtypeStruct tree for AOT lowering (no device allocation); the
    dtypes match ``init_params`` with the same ``dtype_override``."""
    return tree_map_params(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype_override or p.dtype), skel
    )


def param_bytes(skel) -> int:
    leaves = jax.tree_util.tree_leaves(skel, is_leaf=is_param)
    return sum(int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize for p in leaves)


def param_elems(skel) -> int:
    leaves = jax.tree_util.tree_leaves(skel, is_leaf=is_param)
    return sum(int(np.prod(p.shape)) for p in leaves)


# ---------------------------------------------------------------------------
# layer math (pure jnp; activations in cfg.dtype, reductions in f32)
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm_skel(cfg, dim=None):
    d = dim or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"w": Param((d,), ("embed",), init="zeros")}
    return {"w": Param((d,), ("embed",), init="ones"), "b": Param((d,), ("embed",), init="zeros")}


def apply_norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


# Cross-shard partial-sum dtype for TP-sharded contractions.  f32 partials
# mean every TP all-reduce moves f32 activations; bf16 halves the dominant
# collective term (EXPERIMENTS §Perf) at the cost of bf16 accumulation
# across the (16-way) model shards.  Set via set_matmul_partial_dtype.
MATMUL_PARTIAL_DTYPE = [jnp.float32]


def set_matmul_partial_dtype(dtype) -> None:
    MATMUL_PARTIAL_DTYPE[0] = dtype


def dense(x, w):
    """x @ w; MXU accumulates f32 per tile, cross-shard partials use the
    configured dtype (see MATMUL_PARTIAL_DTYPE)."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=MATMUL_PARTIAL_DTYPE[0],
    ).astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_3d, theta: float, sections: Tuple[int, ...]):
    """Qwen2-VL multimodal RoPE.

    positions_3d: (3, ..., S) -- temporal / height / width position ids
    (for text all three streams are equal).  The head-dim frequency bands
    are split into ``sections`` (per half-dim), each band rotated by its
    own position stream.
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(d, theta)  # (half,)
    # build the per-band position tensor: (..., S, half)
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # static
    pos = jnp.take(positions_3d, sec_id, axis=0)  # (half, ..., S)
    pos = jnp.moveaxis(pos, 0, -1)  # (..., S, half)
    angles = pos.astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int):
    """Whisper-style absolute sinusoidal embeddings."""
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    inv = 1.0 / (10000 ** (dim / max(1, d_model // 2 - 1)))
    ang = pos * inv
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), dtype=jnp.float32
    )
