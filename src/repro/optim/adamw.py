"""AdamW with f32 moments, global-norm clipping, warmup+cosine schedule.

Pure-pytree implementation (no optax dependency).  Moment tensors shard
exactly like their parameters (the state skeleton mirrors the param
skeleton), which is what makes 72B-param training fit: params bf16 +
2x f32 moments sharded over data x model = 2.8 GB/chip for qwen2-vl-72b.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1


def schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(1, cfg.warmup_steps))
    prog = jnp.clip(
        (step - cfg.warmup_steps) / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def init_opt_state(params) -> Dict[str, Any]:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(zeros32, params),
        "v": jax.tree_util.tree_map(zeros32, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))


@jax.named_scope("optimizer")
def adamw_update(grads, opt_state, params, cfg: AdamWConfig):
    """Returns (new_params, new_opt_state, metrics)."""
    count = opt_state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    lr = schedule(cfg, count)
    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        step = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
        return newp, m, v

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    flat_p = treedef.flatten_up_to(params)
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "count": count}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
