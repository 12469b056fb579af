"""Mixture-of-Experts FFN: top-k routing, dense dispatch, EP/TP sharding.

Dispatch uses the dense (one-hot combine) formulation: every expert
computes on every token and results are combined with routing weights.
Under GSPMD with experts sharded over the model axis (EP) this lowers to
an all-to-all-free einsum program whose FLOPs are E/top_k times the active
FLOPs -- a low model-FLOPs utilization (``benchmarks/chip/flops/`` over
the peak in ``benchmarks/chip/peaks.json``) exposes exactly this, and the
hillclimb replaces it with a gather-based dispatch (capacity-bounded) for
the MoE cells.

A gather-based (capacity-factor) dispatch is also provided
(``moe_fwd_dropping``) and is selected by ``mode='dropping'``: tokens are
routed to experts via a capacity-C gather, computed, and scattered back --
active-FLOPs-proportional, at the cost of token dropping beyond capacity.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import MATMUL_PARTIAL_DTYPE, Param, dense, gelu


def ffn_skel(cfg, expert_dim: int = 0):
    """Plain FFN (swiglu or gelu).  With expert_dim > 0, weights get a
    leading expert axis."""
    d, f = cfg.d_model, cfg.d_ff
    e = (expert_dim,) if expert_dim else ()
    ax = ("expert",) if expert_dim else ()
    if cfg.act == "swiglu":
        return {
            "wi": Param(e + (d, f), ax + ("embed", "mlp")),
            "wg": Param(e + (d, f), ax + ("embed", "mlp")),
            "wo": Param(e + (f, d), ax + ("mlp", "embed")),
        }
    return {
        "wi": Param(e + (d, f), ax + ("embed", "mlp")),
        "wo": Param(e + (f, d), ax + ("mlp", "embed")),
    }


def ffn_fwd(cfg, p, x):
    if cfg.act == "swiglu":
        h = jax.nn.silu(dense(x, p["wg"]).astype(jnp.float32)).astype(x.dtype) * dense(x, p["wi"])
    else:
        h = gelu(dense(x, p["wi"]).astype(jnp.float32)).astype(x.dtype)
    return dense(h, p["wo"])


# Dispatch mode: "dense" (every expert computes every token -- simple,
# E/top_k x the active FLOPs) or "dropping" (capacity-bounded gather
# dispatch, active-FLOPs-proportional).  §Perf hillclimb knob.
MOE_MODE = ["dense"]


def set_moe_mode(mode: str) -> None:
    assert mode in ("dense", "dropping")
    MOE_MODE[0] = mode


def moe_skel(cfg):
    s = {
        "router": Param((cfg.d_model, cfg.num_experts), ("embed", None), scale=0.1),
        "experts": ffn_skel(cfg, expert_dim=cfg.num_experts),
    }
    if cfg.shared_expert:
        s["shared"] = ffn_skel(cfg)
    return s


def _route(cfg, p, x):
    """Router: returns (weights (B,S,E) with zeros off the top-k, aux loss)."""
    logits = dense(x, p["router"]).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)  # (B,S,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)  # (B,S,k,E)
    weights = (onehot * topw[..., None]).sum(-2)  # (B,S,E)
    # Switch-style load-balancing auxiliary loss.
    frac_tokens = onehot.sum(-2).mean(axis=(0, 1))  # (E,)
    frac_probs = probs.mean(axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(frac_tokens * frac_probs)
    return weights, aux


def moe_fwd(cfg, p, x) -> Tuple[jax.Array, jax.Array]:
    """Dense-dispatch MoE: out = sum_e w_e * FFN_e(x).  (B,S,d) -> same."""
    weights, aux = _route(cfg, p, x)
    ex = p["experts"]
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,edf->ebsf", x, ex["wg"], preferred_element_type=jnp.float32)
        h = jnp.einsum("bsd,edf->ebsf", x, ex["wi"], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * h).astype(x.dtype)
    else:
        h = jnp.einsum("bsd,edf->ebsf", x, ex["wi"], preferred_element_type=jnp.float32)
        h = gelu(h).astype(x.dtype)
    # Combine-before-reduce: weighting h by the router FIRST and contracting
    # (e, f) in one dot keeps the cross-shard partial at (B,S,d).  The naive
    # order (sum over f, then weight) makes GSPMD all-reduce the full
    # (E,B,S,d) expert outputs -- E x the bytes (8.3 TB/step on mixtral
    # train_4k; EXPERIMENTS §Perf iteration 4).
    hw = h * weights.transpose(2, 0, 1)[:, :, :, None].astype(h.dtype)  # (E,B,S,f)
    out = jnp.einsum(
        "ebsf,efd->bsd", hw, ex["wo"],
        preferred_element_type=MATMUL_PARTIAL_DTYPE[0],
    )
    out = out.astype(x.dtype)
    if cfg.shared_expert:
        out = out + ffn_fwd(cfg, p["shared"], x)
    return out, aux


def moe_fwd_dropping(cfg, p, x, capacity_factor: float = 1.25):
    """Gather-based dispatch with per-expert capacity (beyond-paper perf
    path): FLOPs proportional to active params, tokens over capacity drop
    to the residual stream."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    weights, aux = _route(cfg, p, x)  # (B,S,E)
    cap = int(capacity_factor * B * S * k / E) or 1
    flat_w = weights.reshape(B * S, E)  # (T,E)
    # positions of each token within its expert queue
    sel = flat_w > 0  # (T,E)
    pos_in_e = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1  # (T,E)
    keep = sel & (pos_in_e < cap)
    xt = x.reshape(B * S, d)
    t_idx = jnp.broadcast_to(jnp.arange(B * S)[:, None], (B * S, E))
    e_idx = jnp.broadcast_to(jnp.arange(E)[None, :], (B * S, E))
    slot = jnp.where(keep, pos_in_e, cap)  # cap = drop bucket
    # token id occupying each (expert, slot); int scatter then gather --
    # avoids materializing a (T, E, d) tensor.
    token_for_slot = jnp.zeros((E, cap + 1), jnp.int32)
    token_for_slot = token_for_slot.at[e_idx.reshape(-1), slot.reshape(-1)].max(
        t_idx.reshape(-1).astype(jnp.int32)
    )
    dis = xt[token_for_slot[:, :cap]]  # (E, cap, d)
    ex = p["experts"]
    if cfg.act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", dis, ex["wg"], preferred_element_type=jnp.float32)
        h = jnp.einsum("ecd,edf->ecf", dis, ex["wi"], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * h).astype(x.dtype)
    else:
        h = gelu(
            jnp.einsum("ecd,edf->ecf", dis, ex["wi"], preferred_element_type=jnp.float32)
        ).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, ex["wo"], preferred_element_type=jnp.float32)
    # combine back
    w_slot = jnp.where(keep, flat_w, 0.0)  # (T,E)
    y_pad = jnp.concatenate([y, jnp.zeros((E, 1, d), y.dtype)], axis=1)
    gathered = y_pad[e_idx.reshape(-1), slot.reshape(-1)].reshape(B * S, E, d)
    out = jnp.einsum("ted,te->td", gathered, w_slot.astype(jnp.float32))
    out = out.reshape(B, S, d).astype(x.dtype)
    if cfg.shared_expert:
        out = out + ffn_fwd(cfg, p["shared"], x)
    return out, aux
