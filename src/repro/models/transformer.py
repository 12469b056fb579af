"""Model assembly: embed -> scanned layer stages -> norm -> lm head.

Layer stacks are scanned over *blocks* (one block = one repeat of the
config's layer pattern) with parameters stacked on a leading "layers"
axis -- compile time stays bounded for 80-layer models because the HLO
contains one block body, not eighty layers.

Three entry points per model:
  * train_loss(params, batch)           -> scalar loss (+aux)
  * prefill(params, batch)              -> last-token logits, caches
  * decode_step(params, token, t, caches)-> logits, updated caches

Caches are pytrees mirroring the stage structure: attention positions get
ring/linear KV caches, mamba positions get (conv, ssm) states, rwkv
positions get (shift, wkv) states, cross-attention gets static encoder KV.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    Param,
    apply_norm,
    dense,
    norm_skel,
    sinusoidal_positions,
    tree_map_params,
)


# ---------------------------------------------------------------------------
# skeletons
# ---------------------------------------------------------------------------


def layer_skel(cfg: ModelConfig, spec: LayerSpec, cross: bool = False):
    s: Dict[str, Any] = {"ln1": norm_skel(cfg)}
    if spec.kind == "attn":
        s["attn"] = attn.attn_skel(cfg)
    elif spec.kind == "mamba":
        s["mixer"] = ssm_mod.mamba_skel(cfg)
    elif spec.kind == "mamba2":
        s["mixer"] = ssm_mod.mamba2_skel(cfg)
    elif spec.kind == "rwkv":
        s["rwkv"] = ssm_mod.rwkv_skel(cfg)
        s["ln2"] = norm_skel(cfg)
        return s  # rwkv block embeds its own channel-mix FFN
    else:
        raise ValueError(spec.kind)
    if cross:
        s["ln_cross"] = norm_skel(cfg)
        s["cross"] = attn.attn_skel(cfg, cross=True)
    s["ln2"] = norm_skel(cfg)
    if spec.moe:
        s["moe"] = moe_mod.moe_skel(cfg)
    else:
        s["ffn"] = moe_mod.ffn_skel(cfg)
    return s


def _stack(skel, n: int):
    return tree_map_params(
        lambda p: Param((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale, p.dtype),
        skel,
    )


def stage_skel(cfg: ModelConfig, pattern, nblocks: int, cross: bool = False):
    per_block = {f"pos{i}": layer_skel(cfg, s, cross) for i, s in enumerate(pattern)}
    return _stack(per_block, nblocks)


def model_skel(cfg: ModelConfig):
    V, d = cfg.padded_vocab, cfg.d_model
    s: Dict[str, Any] = {
        # Embedding-table layout is constrained by the XLA gather
        # partitioner: vocab-sharded tables force full-table remat, and a
        # "data"(FSDP)-sharded d_model dim crashes the legacy SPMD
        # partitioner inside manual-pod shard_map (b/433785288).  TP
        # ("heads"->model) sharding of d_model is the layout that both
        # partitions cleanly and survives the manual-pod path.  The output
        # projection (lm_head) IS vocab-sharded -- a matmul partitions fine.
        "embed": Param((V, d), (None, "heads"), scale=1.0),
        "final_norm": norm_skel(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Param((d, V), ("embed", "vocab"))
    s["stages"] = [
        stage_skel(cfg, pattern, nblocks, cross=cfg.is_encoder_decoder)
        for pattern, nblocks in cfg.stages()
    ]
    if cfg.is_encoder_decoder:
        s["encoder"] = {
            "stage": stage_skel(
                cfg, (LayerSpec(kind="attn"),), cfg.encoder_layers, cross=False
            ),
            "final_norm": norm_skel(cfg),
        }
    return s


# ---------------------------------------------------------------------------
# layer forward (train / prefill / decode)
# ---------------------------------------------------------------------------


def _residual(cfg, x, y):
    """x + y, the branch y scaled by ``cfg.residual_multiplier`` where that is not 1."""
    r = cfg.residual_multiplier
    return x + y if r == 1.0 else x + y * jnp.asarray(r, y.dtype)


@jax.named_scope("mlp")
def _ffn_part(cfg, lp, spec, x):
    h = apply_norm(cfg, lp["ln2"], x)
    if spec.moe:
        if moe_mod.MOE_MODE[0] == "dropping":
            out, aux = moe_mod.moe_fwd_dropping(cfg, lp["moe"], h)
        else:
            out, aux = moe_mod.moe_fwd(cfg, lp["moe"], h)
    else:
        out, aux = moe_mod.ffn_fwd(cfg, lp["ffn"], h), 0.0
    return _residual(cfg, x, out), aux


def layer_fwd(cfg, spec, lp, x, q_pos, positions_3d=None, enc_out=None, causal=True):
    """Full-sequence forward (training / prefill trunk)."""
    if spec.kind == "rwkv":
        return (
            ssm_mod.rwkv_fwd(
                cfg, lp["rwkv"], x,
                lambda t: apply_norm(cfg, lp["ln1"], t),
                lambda t: apply_norm(cfg, lp["ln2"], t),
            ),
            0.0,
        )
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.kind == "attn":
        x = _residual(cfg, x, attn.attention_fwd(
            cfg, lp["attn"], h, spec, q_pos, positions_3d, causal=causal
        ))
    elif spec.kind == "mamba2":
        x = _residual(cfg, x, ssm_mod.mamba2_fwd(cfg, lp["mixer"], h))
    else:  # mamba
        x = _residual(cfg, x, ssm_mod.mamba_fwd(cfg, lp["mixer"], h))
    if enc_out is not None and "cross" in lp:
        h = apply_norm(cfg, lp["ln_cross"], x)
        x = _residual(cfg, x, attn.attention_fwd(
            cfg, lp["cross"], h, spec, q_pos, kv_x=enc_out
        ))
    return _ffn_part(cfg, lp, spec, x)


def layer_prefill(cfg, spec, lp, x, q_pos, cache_len, positions_3d=None, enc_out=None):
    """Forward + produce this layer's decode cache."""
    if spec.kind == "rwkv":
        out, state = ssm_mod.rwkv_prefill(
            cfg, lp["rwkv"], x,
            lambda t: apply_norm(cfg, lp["ln1"], t),
            lambda t: apply_norm(cfg, lp["ln2"], t),
        )
        return out, 0.0, state
    h = apply_norm(cfg, lp["ln1"], x)
    cache = None
    if spec.kind == "attn":
        x = _residual(cfg, x, attn.attention_fwd(cfg, lp["attn"], h, spec, q_pos, positions_3d))
        k, v = attn.attention_prefill_kv(cfg, lp["attn"], h, q_pos, positions_3d)
        B, S = k.shape[0], k.shape[1]
        C = cache_len
        kc = jnp.zeros((B, C, cfg.num_kv_heads, cfg.head_dim), k.dtype)
        vc = jnp.zeros_like(kc)
        if C >= S:
            kc = lax.dynamic_update_slice_in_dim(kc, k, 0, axis=1)
            vc = lax.dynamic_update_slice_in_dim(vc, v, 0, axis=1)
        else:  # ring cache: keep the last C positions at slots pos % C
            roll = S % C
            kw = k[:, -C:]
            vw = v[:, -C:]
            kc = jnp.roll(kw, roll, axis=1)
            vc = jnp.roll(vw, roll, axis=1)
        cache = {"k": kc, "v": vc}
    else:  # mamba, mamba2
        prefill = ssm_mod.mamba2_prefill if spec.kind == "mamba2" else ssm_mod.mamba_prefill
        y, cache = prefill(cfg, lp["mixer"], h)
        x = _residual(cfg, x, y)
    if enc_out is not None and "cross" in lp:
        hc = apply_norm(cfg, lp["ln_cross"], x)
        x = _residual(cfg, x, attn.attention_fwd(cfg, lp["cross"], hc, spec, q_pos, kv_x=enc_out))
        ek = dense(enc_out, lp["cross"]["wk"]).reshape(
            enc_out.shape[0], enc_out.shape[1], cfg.num_kv_heads, cfg.head_dim
        )
        ev = dense(enc_out, lp["cross"]["wv"]).reshape(ek.shape)
        cache = {"self": cache, "cross_k": ek, "cross_v": ev}
    x, aux = _ffn_part(cfg, lp, spec, x)
    return x, aux, cache


def layer_decode(cfg, spec, lp, x, t, cache):
    """One-token forward against the cache."""
    if spec.kind == "rwkv":
        out, state = ssm_mod.rwkv_decode(
            cfg, lp["rwkv"], x, cache,
            lambda z: apply_norm(cfg, lp["ln1"], z),
            lambda z: apply_norm(cfg, lp["ln2"], z),
        )
        return out, state
    has_cross = isinstance(cache, dict) and "cross_k" in cache
    self_cache = cache["self"] if has_cross else cache
    h = apply_norm(cfg, lp["ln1"], x)
    if spec.kind == "attn":
        out, (kc, vc) = attn.attention_decode(
            cfg, lp["attn"], h, spec, (self_cache["k"], self_cache["v"]), t
        )
        x = _residual(cfg, x, out)
        new_self = {"k": kc, "v": vc}
    else:
        decode = ssm_mod.mamba2_decode if spec.kind == "mamba2" else ssm_mod.mamba_decode
        y, new_self = decode(cfg, lp["mixer"], h, self_cache)
        x = _residual(cfg, x, y)
    if has_cross:
        hc = apply_norm(cfg, lp["ln_cross"], x)
        out, _ = attn.attention_decode(
            cfg, lp["cross"], hc, spec, (cache["cross_k"], cache["cross_v"]), t,
            cross=True,
        )
        x = _residual(cfg, x, out)
        new_cache = {"self": new_self, "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    else:
        new_cache = new_self
    x, _ = _ffn_part(cfg, lp, spec, x)
    return x, new_cache


# ---------------------------------------------------------------------------
# stage runners (scan over blocks)
# ---------------------------------------------------------------------------


def stage_fwd(cfg, pattern, stage_params, x, q_pos, positions_3d=None, enc_out=None, causal=True):
    def body(carry, block_params):
        h, aux = carry
        # Pin the block carry to batch-sharded: without this, XLA's cost
        # model sometimes all-gathers activations over the FSDP axis and
        # runs every block with a replicated batch (observed 7x FLOPs).
        h = _constrain(h, ("batch", None, None))
        for i, spec in enumerate(pattern):
            h, a = layer_fwd(
                cfg, spec, block_params[f"pos{i}"], h, q_pos, positions_3d, enc_out,
                causal=causal,
            )
            aux = aux + a
        return (h, aux), None

    (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), stage_params)
    return x, aux


def cache_len_for(cfg, spec: LayerSpec, seq_len: int) -> int:
    if spec.kind != "attn":
        return 0  # state caches are fixed-size
    if spec.attention == "window":
        return min(seq_len, spec.window)
    return seq_len


def stage_prefill(cfg, pattern, stage_params, x, q_pos, cache_seq, positions_3d=None, enc_out=None):
    def body(carry, block_params):
        h, aux = carry
        h = _constrain(h, ("batch", None, None))
        caches = {}
        for i, spec in enumerate(pattern):
            h, a, c = layer_prefill(
                cfg, spec, block_params[f"pos{i}"], h, q_pos,
                cache_len_for(cfg, spec, cache_seq), positions_3d, enc_out,
            )
            aux = aux + a
            caches[f"pos{i}"] = c
        return (h, aux), caches

    (x, aux), caches = lax.scan(body, (x, jnp.float32(0.0)), stage_params)
    return x, aux, caches


def stage_decode(cfg, pattern, stage_params, x, t, caches):
    def body(h, xs):
        block_params, cache = xs
        h = _constrain(h, ("batch", None, None))
        new = {}
        for i, spec in enumerate(pattern):
            h, c = layer_decode(cfg, spec, block_params[f"pos{i}"], h, t, cache[f"pos{i}"])
            new[f"pos{i}"] = c
        return h, new

    x, new_caches = lax.scan(body, x, (stage_params, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# model entry points
# ---------------------------------------------------------------------------


# Activation-sharding policy, set by the launcher before tracing (the
# model code itself is mesh-agnostic).  "batch" -> dp mesh axes for the
# activation batch dim, "tp" -> the model/TP axis.  GSPMD propagates most
# shardings, but the loss-side (B,S,V) tensors need explicit constraints:
# without them the partitioner materializes them fully replicated
# (observed: 52 GiB/device for stablelm train_4k).
ACTIVATION_SHARDING: Dict[str, Any] = {"batch": None, "tp": None}


def set_activation_sharding(batch_axes, tp_axis) -> None:
    ACTIVATION_SHARDING["batch"] = batch_axes
    ACTIVATION_SHARDING["tp"] = tp_axis


def _constrain(x, dims):
    """dims: tuple of policy keys / None per array dim."""
    from jax.sharding import PartitionSpec as P

    if ACTIVATION_SHARDING["batch"] is None and ACTIVATION_SHARDING["tp"] is None:
        return x
    spec = P(*[ACTIVATION_SHARDING.get(d) if d else None for d in dims])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except ValueError:
        return x  # no mesh in context (pure-CPU smoke paths)


def _embed(cfg, params, tokens):
    # NOTE: no sharding constraint directly on the gather output -- the
    # SPMD partitioner mis-compiles gather+reshard (invalid dynamic-slice);
    # propagation from the batch-sharded indices is correct on its own.
    return _scale_embed(cfg, jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype)))


def _scale_embed(cfg, x):
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _unembed(cfg, params, x):
    w = params.get("lm_head", None)
    if w is None:
        w = params["embed"].T
    # FSDP weight-gather: all-gathering the (d, V/tp) weight shard (~0.3 GB
    # bf16) beats all-reducing the (B, S, V/tp) f32 logits (~3 GB/micro) --
    # the constraint forces XLA into the weight-stationary plan.
    w = _constrain(w, (None, "tp"))
    logits = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return logits if cfg.logits_divisor == 1.0 else logits / cfg.logits_divisor


def _run_encoder(cfg, params, enc_frames):
    pos = sinusoidal_positions(enc_frames.shape[1], cfg.d_model)
    h = enc_frames.astype(jnp.dtype(cfg.dtype)) + pos[None].astype(jnp.dtype(cfg.dtype))
    q_pos = jnp.arange(enc_frames.shape[1], dtype=jnp.int32)
    h, _ = stage_fwd(
        cfg, (LayerSpec(kind="attn"),), params["encoder"]["stage"], h, q_pos,
        causal=False,  # encoder self-attention is bidirectional
    )
    return apply_norm(cfg, params["encoder"]["final_norm"], h)


def forward(cfg: ModelConfig, params, batch) -> jax.Array:
    """Full-sequence logits (B, S, V_padded) in f32.

    ``batch["x_embed"]`` (precomputed embeddings) takes precedence over
    ``batch["tokens"]``: the microbatched train step hoists the embedding
    gather out of its accumulation scan (XLA's SPMD partitioner
    mis-compiles gathers inside while bodies at 256+ devices)."""
    if "x_embed" in batch:
        x = _scale_embed(cfg, batch["x_embed"].astype(jnp.dtype(cfg.dtype)))
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = _embed(cfg, params, tokens)
    if cfg.rope == "none" and not cfg.is_encoder_decoder and cfg.family != "ssm" and cfg.family != "hybrid":
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    if cfg.is_encoder_decoder:
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
        enc_out = _run_encoder(cfg, params, batch["encoder_frames"])
    else:
        enc_out = None
    q_pos = jnp.arange(S, dtype=jnp.int32)
    positions_3d = batch.get("positions_3d") if cfg.rope == "mrope" else None
    aux_total = jnp.float32(0.0)
    for (pattern, _n), sp in zip(cfg.stages(), params["stages"]):
        x, aux = stage_fwd(cfg, pattern, sp, x, q_pos, positions_3d, enc_out)
        aux_total = aux_total + aux
    x = apply_norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    return logits, aux_total


def train_loss(cfg: ModelConfig, params, batch):
    """Next-token cross-entropy + MoE aux loss.

    The label log-prob is extracted with a one-hot contraction rather than
    take_along_axis: a gather over the vocab-sharded logits forces the XLA
    SPMD partitioner to replicate the full (B,S,V) tensor per device
    (observed: 52 GiB/device on the stablelm train_4k dry-run); the
    elementwise one-hot product partitions cleanly over the model axis.
    """
    logits, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    V = logits.shape[-1]
    logits32 = _constrain(logits.astype(jnp.float32), ("batch", None, "tp"))
    lse = jax.nn.logsumexp(logits32, axis=-1)  # (B,S)
    onehot = _constrain(
        jax.nn.one_hot(labels, V, dtype=jnp.float32), ("batch", None, "tp")
    )
    picked = jnp.sum(logits32 * onehot, axis=-1)  # (B,S)
    ll = picked - lse
    mask = (labels >= 0).astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.num_experts:
        loss = loss + cfg.router_aux_weight * aux / max(1, cfg.num_layers)
    return loss


def prefill(cfg: ModelConfig, params, batch, cache_seq: int):
    """Process the prompt; return (last-token logits, caches)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    if cfg.is_encoder_decoder:
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
        enc_out = _run_encoder(cfg, params, batch["encoder_frames"])
    else:
        enc_out = None
    q_pos = jnp.arange(S, dtype=jnp.int32)
    positions_3d = batch.get("positions_3d") if cfg.rope == "mrope" else None
    all_caches = []
    for (pattern, _n), sp in zip(cfg.stages(), params["stages"]):
        x, _aux, caches = stage_prefill(
            cfg, pattern, sp, x, q_pos, cache_seq, positions_3d, enc_out
        )
        all_caches.append(caches)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x[:, -1:])
    return logits[:, 0], all_caches


def decode_step(cfg: ModelConfig, params, token, t, caches):
    """One decode step: token (B,1) int32, t scalar position."""
    x = _embed(cfg, params, token)
    if cfg.is_encoder_decoder:
        pe = sinusoidal_positions(8192, cfg.d_model)
        x = x + lax.dynamic_slice_in_dim(pe, jnp.minimum(t, 8191), 1, axis=0)[None].astype(x.dtype)
    new_caches = []
    for (pattern, _n), sp, cs in zip(cfg.stages(), params["stages"], caches):
        x, nc = stage_decode(cfg, pattern, sp, x, t, cs)
        new_caches.append(nc)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    return logits[:, 0], new_caches


# ---------------------------------------------------------------------------
# abstract cache construction (for dry-run serve_step lowering)
# ---------------------------------------------------------------------------


def cache_skel(cfg: ModelConfig, batch: int, seq_len: int):
    """Abstract cache pytree (ShapeDtypeStructs) for a given shape cell."""
    dt = jnp.dtype(cfg.dtype)

    def one_layer(spec: LayerSpec):
        if spec.kind == "attn":
            C = cache_len_for(cfg, spec, seq_len)
            kv = {
                "k": jax.ShapeDtypeStruct((batch, C, cfg.num_kv_heads, cfg.head_dim), dt),
                "v": jax.ShapeDtypeStruct((batch, C, cfg.num_kv_heads, cfg.head_dim), dt),
            }
            if cfg.is_encoder_decoder:
                E = cfg.encoder_seq
                return {
                    "self": kv,
                    "cross_k": jax.ShapeDtypeStruct(
                        (batch, E, cfg.num_kv_heads, cfg.head_dim), dt
                    ),
                    "cross_v": jax.ShapeDtypeStruct(
                        (batch, E, cfg.num_kv_heads, cfg.head_dim), dt
                    ),
                }
            return kv
        if spec.kind == "mamba":
            di = cfg.ssm_expand * cfg.d_model
            return {
                "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv_width - 1, di), dt),
                "ssm": jax.ShapeDtypeStruct((batch, di, cfg.ssm_state_dim), jnp.float32),
            }
        if spec.kind == "mamba2":
            H, P, _, N, conv = ssm_mod.mamba2_dims(cfg)
            return {
                "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv_width - 1, conv), dt),
                "ssm": jax.ShapeDtypeStruct((batch, H, P, N), jnp.float32),
            }
        if spec.kind == "rwkv":
            d = cfg.d_model
            hs = cfg.rwkv_head_size
            return {
                "shift_t": jax.ShapeDtypeStruct((batch, d), dt),
                "shift_c": jax.ShapeDtypeStruct((batch, d), dt),
                "wkv": jax.ShapeDtypeStruct((batch, d // hs, hs, hs), jnp.float32),
            }
        raise ValueError(spec.kind)

    def stack(tree, n):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree
        )

    out = []
    for pattern, nblocks in cfg.stages():
        out.append(
            stack({f"pos{i}": one_layer(s) for i, s in enumerate(pattern)}, nblocks)
        )
    return out


def cache_spec_skel(cfg: ModelConfig, b_ax, seq_ax, tp_ax):
    """PartitionSpec pytree structurally mirroring :func:`cache_skel`.

    b_ax: batch mesh axes (or None); seq_ax: cache-length mesh axes;
    tp_ax: model axis for state inner dims.  Leading dim is the stacked
    layers axis (never sharded).
    """
    from jax.sharding import PartitionSpec as P

    def one_layer(spec: LayerSpec):
        if spec.kind == "attn":
            kv = {
                "k": P(None, b_ax, seq_ax, None, None),
                "v": P(None, b_ax, seq_ax, None, None),
            }
            if cfg.is_encoder_decoder:
                return {
                    "self": kv,
                    "cross_k": P(None, b_ax, None, None, None),
                    "cross_v": P(None, b_ax, None, None, None),
                }
            return kv
        if spec.kind == "mamba":
            return {
                "conv": P(None, b_ax, None, tp_ax),
                "ssm": P(None, b_ax, tp_ax, None),
            }
        if spec.kind == "mamba2":
            return {"conv": P(None, b_ax, None, None), "ssm": P(None, b_ax, None, None, None)}
        if spec.kind == "rwkv":
            return {
                "shift_t": P(None, b_ax, tp_ax),
                "shift_c": P(None, b_ax, tp_ax),
                "wkv": P(None, b_ax, tp_ax, None, None),
            }
        raise ValueError(spec.kind)

    out = []
    for pattern, _nblocks in cfg.stages():
        out.append({f"pos{i}": one_layer(s) for i, s in enumerate(pattern)})
    return out
