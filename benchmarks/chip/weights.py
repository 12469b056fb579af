"""Seeded weights, keyed by each parameter's path.

The benchmark makes the weights, not the program: the program's state and
the reference both take their values from ``leaf_value`` for the same
(seed, path, shape), so the reference owes nothing to the program's own
initializer.  Layer-norm scales start at 1 and shifts at 0; every other
parameter is drawn from N(0, 0.02^2), the initializer scale Whisper and
StarCoder2 publish (``init_std`` 0.02, ``initializer_range`` 0.018).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def base_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def path_str(keypath) -> str:
    """``("stages", 0, "pos0", "attn", "wq")`` -> ``"stages/0/pos0/attn/wq"``."""
    parts = []
    for k in keypath:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def is_norm(path: str) -> bool:
    parent = path.split("/")[-2] if "/" in path else ""
    return parent.startswith("ln") or parent.endswith("norm")


def leaf_value(key, path: str, shape, dtype):
    """The value of parameter ``path``; traceable, so it runs inside jit."""
    if is_norm(path):
        fill = jnp.ones if path.endswith("/w") else jnp.zeros
        return fill(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * INIT_STD).astype(dtype)


def tree_values(key, abstract_tree):
    """``leaf_value`` for every leaf of a tree of ShapeDtypeStructs."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, a: leaf_value(key, path_str(kp), a.shape, a.dtype), abstract_tree
    )
