"""State-space blocks: Mamba (Jamba's mixer) and RWKV-6 ("Finch").

Both are linear-state recurrences implemented with ``lax.scan`` over time
(the TPU-friendly chunked-parallel form is a §Perf hillclimb option for
the SSM cells; the scan form is the correctness baseline and is what the
dry-run lowers).  Decode carries O(1) state per layer -- these are the
architectures for which long_500k is the showcase cell.

Shapes use (B, S, d) activations; state trees are dicts of arrays so the
serving engine can thread them generically like KV caches.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import Param, dense


def chunked_scan(step, init, xs, seq_len: int, chunk: int = 128):
    """lax.scan with chunked state checkpointing.

    Reverse-mode through a plain scan stacks the carry (the SSM state) for
    every timestep -- for mamba that is (B, di, N) x S x layers of HBM
    traffic and made jamba train_4k memory-bound by ~200x (EXPERIMENTS
    §Perf iteration 1).  The standard selective-scan strategy: save the
    state only at chunk boundaries and recompute within chunks in the
    backward sweep (jax.checkpoint around an inner scan).
    """
    while seq_len % chunk:
        chunk //= 2
    nchunks = seq_len // chunk

    def reshape_xs(x):
        return x.reshape((nchunks, chunk) + x.shape[1:])

    xs_c = jax.tree_util.tree_map(reshape_xs, xs)

    @jax.checkpoint
    def inner(h, xc):
        return lax.scan(step, h, xc)

    def outer(h, xc):
        h2, ys = inner(h, xc)
        return h2, ys

    h, ys_c = lax.scan(outer, init, xs_c)
    ys = jax.tree_util.tree_map(
        lambda y: y.reshape((seq_len,) + y.shape[2:]), ys_c
    )
    return h, ys


# ---------------------------------------------------------------------------
# Mamba (selective SSM, as interleaved in Jamba)
# ---------------------------------------------------------------------------


def mamba_skel(cfg):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N = cfg.ssm_state_dim
    dt_rank = max(1, d // 16)
    return {
        "in_proj": Param((d, 2 * di), ("embed", "ssm")),
        "conv_w": Param((cfg.ssm_conv_width, di), (None, "ssm"), scale=0.5),
        "conv_b": Param((di,), ("ssm",), init="zeros"),
        "x_proj": Param((di, dt_rank + 2 * N), ("ssm", None)),
        "dt_w": Param((dt_rank, di), (None, "ssm")),
        "dt_b": Param((di,), ("ssm",), init="zeros"),
        "A_log": Param((di, N), ("ssm", None), init="ones"),
        "D": Param((di,), ("ssm",), init="ones"),
        "out_proj": Param((di, d), ("ssm", "embed")),
    }


def _mamba_core(cfg, p, xz, conv_state, ssm_state, *, single_step: bool):
    """Shared selective-scan core.

    xz: (B, S, 2*di).  conv_state: (B, W-1, di).  ssm_state: (B, di, N).
    Returns (y (B,S,d-in-di), new conv_state, new ssm_state).
    """
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N = cfg.ssm_state_dim
    W = cfg.ssm_conv_width
    dt_rank = max(1, d // 16)
    x, z = jnp.split(xz, 2, axis=-1)  # (B,S,di) each
    B_, S = x.shape[:2]

    # causal depthwise conv over time (width W)
    xpad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # (B, S+W-1, di)
    new_conv_state = xpad[:, -(W - 1):, :] if W > 1 else conv_state
    conv = sum(
        xpad[:, i : i + S, :] * p["conv_w"][i][None, None, :] for i in range(W)
    ) + p["conv_b"][None, None, :]
    x = jax.nn.silu(conv.astype(jnp.float32)).astype(x.dtype)

    proj = dense(x, p["x_proj"])  # (B,S,dt_rank+2N)
    dt, Bmat, Cmat = jnp.split(proj, [dt_rank, dt_rank + N], axis=-1)
    delta = jax.nn.softplus(
        dense(dt, p["dt_w"]).astype(jnp.float32) + p["dt_b"].astype(jnp.float32)
    )  # (B,S,di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (di,N)

    # Discretization (dA = exp(delta (x) A), dBx = delta*B*x) is FUSED into
    # the scan body: materializing the (B,S,di,N) tensors costs N=16x the
    # scan's HBM traffic and made jamba train_4k memory-bound by ~3 orders
    # of magnitude: the dry-run's bytes over the HBM bandwidth in
    # benchmarks/chip/peaks.json, against its FLOPs (benchmarks/chip/flops/).
    def step(h, inp):
        delta_t, B_t, C_t, x_t = inp  # (B,di), (B,N), (B,N), (B,di)
        dA_t = jnp.exp(delta_t[..., None] * A[None])  # (B,di,N), VMEM-local
        dBx_t = delta_t[..., None] * B_t[:, None, :] * x_t[..., None]
        h = dA_t * h + dBx_t
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    if single_step:
        h, y = step(
            ssm_state,
            (
                delta[:, 0],
                Bmat[:, 0].astype(jnp.float32),
                Cmat[:, 0].astype(jnp.float32),
                x[:, 0].astype(jnp.float32),
            ),
        )
        ys = y[:, None]
        new_ssm_state = h
    else:
        xs = (
            delta.transpose(1, 0, 2),
            Bmat.transpose(1, 0, 2).astype(jnp.float32),
            Cmat.transpose(1, 0, 2).astype(jnp.float32),
            x.transpose(1, 0, 2).astype(jnp.float32),
        )
        new_ssm_state, ys = chunked_scan(step, ssm_state, xs, S)
        ys = ys.transpose(1, 0, 2)  # (B,S,di)
    y = ys + x.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(xz.dtype)
    return y, new_conv_state, new_ssm_state


def mamba_init_state(cfg, batch: int, dtype=jnp.float32):
    di = cfg.ssm_expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, di), dtype),
        "ssm": jnp.zeros((batch, di, cfg.ssm_state_dim), jnp.float32),
    }


def mamba_fwd(cfg, p, x):
    """Training/prefill forward (fresh state)."""
    B = x.shape[0]
    xz = dense(x, p["in_proj"])
    st = mamba_init_state(cfg, B, x.dtype)
    y, _, _ = _mamba_core(cfg, p, xz, st["conv"], st["ssm"], single_step=False)
    return dense(y, p["out_proj"])


def mamba_prefill(cfg, p, x):
    """Prefill returning the state for subsequent decode."""
    B = x.shape[0]
    xz = dense(x, p["in_proj"])
    st = mamba_init_state(cfg, B, x.dtype)
    y, conv, ssm = _mamba_core(cfg, p, xz, st["conv"], st["ssm"], single_step=False)
    return dense(y, p["out_proj"]), {"conv": conv, "ssm": ssm}


def mamba_decode(cfg, p, x, state: Dict[str, jax.Array]):
    xz = dense(x, p["in_proj"])  # (B,1,2di)
    y, conv, ssm = _mamba_core(cfg, p, xz, state["conv"], state["ssm"], single_step=True)
    return dense(y, p["out_proj"]), {"conv": conv, "ssm": ssm}


# ---------------------------------------------------------------------------
# RWKV-6 (Finch): data-dependent decay WKV + channel mix
# ---------------------------------------------------------------------------


def rwkv_skel(cfg):
    d = cfg.d_model
    f = cfg.d_ff
    lora = 64
    return {
        "time": {
            "mu": Param((5, d), (None, "embed"), init="zeros"),  # r,k,v,w,g mixes
            "wr": Param((d, d), ("embed", "heads")),
            "wk": Param((d, d), ("embed", "heads")),
            "wv": Param((d, d), ("embed", "heads")),
            "wg": Param((d, d), ("embed", "heads")),
            "wo": Param((d, d), ("heads", "embed")),
            "w0": Param((d,), ("embed",), init="zeros"),
            "w_lora_a": Param((d, lora), ("embed", None), scale=0.1),
            "w_lora_b": Param((lora, d), (None, "embed"), scale=0.1),
            "u": Param((d,), ("embed",), init="zeros"),
            "ln_w": Param((d,), ("embed",), init="ones"),  # per-head groupnorm
            "ln_b": Param((d,), ("embed",), init="zeros"),
        },
        "channel": {
            "mu": Param((2, d), (None, "embed"), init="zeros"),  # k,r mixes
            "wk": Param((d, f), ("embed", "mlp")),
            "wv": Param((f, d), ("mlp", "embed")),
            "wr": Param((d, d), ("embed", "heads")),
        },
    }


def _token_shift(x, prev):
    """shifted[t] = x[t-1]; shifted[0] = prev (carry across calls)."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _wkv6_scan(r, k, v, w, u, state, single_step: bool):
    """WKV-6 recurrence.  r,k,v,w: (B,S,H,hs); u: (H,hs); state: (B,H,hs,hs).

    y_t = (S_t + diag(u) k_t v_t^T)^T r_t ;  S_{t+1} = diag(w_t) S_t + k_t v_t^T
    """
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (B,H,hs) each
        kv = k_t[..., :, None] * v_t[..., None, :]  # (B,H,hs,hs)
        y = jnp.einsum("bhij,bhi->bhj", S + u[None, :, :, None] * kv, r_t)
        S = w_t[..., :, None] * S + kv
        return S, y

    if single_step:
        S, y = step(state, (r[:, 0], k[:, 0], v[:, 0], w[:, 0]))
        return y[:, None], S
    seq = r.shape[1]
    xs = tuple(t.transpose(1, 0, 2, 3) for t in (r, k, v, w))
    S, ys = chunked_scan(step, state, xs, seq)
    return ys.transpose(1, 0, 2, 3), S


def rwkv_init_state(cfg, batch: int, dtype=jnp.float32):
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    H = d // hs
    return {
        "shift_t": jnp.zeros((batch, d), dtype),
        "shift_c": jnp.zeros((batch, d), dtype),
        "wkv": jnp.zeros((batch, H, hs, hs), jnp.float32),
    }


def _rwkv_time_mix(cfg, p, x, shift_prev, wkv_state, single_step):
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    H = d // hs
    B, S = x.shape[:2]
    xx = _token_shift(x, shift_prev)
    mu = p["mu"]  # (5,d)
    xr, xk, xv, xw, xg = (
        x + (xx - x) * jax.nn.sigmoid(mu[i].astype(jnp.float32)).astype(x.dtype)
        for i in range(5)
    )
    r = dense(xr, p["wr"]).reshape(B, S, H, hs).astype(jnp.float32)
    k = dense(xk, p["wk"]).reshape(B, S, H, hs).astype(jnp.float32)
    v = dense(xv, p["wv"]).reshape(B, S, H, hs).astype(jnp.float32)
    g = jax.nn.silu(dense(xg, p["wg"]).astype(jnp.float32))
    # data-dependent decay (the Finch contribution)
    w_dd = jnp.tanh(dense(xw, p["w_lora_a"]).astype(jnp.float32))
    w_dd = jax.lax.dot_general(
        w_dd, p["w_lora_b"].astype(jnp.float32),
        (((w_dd.ndim - 1,), (0,)), ((), ())),
    )
    w = jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32)[None, None] + w_dd))  # (B,S,d) in (0,1)
    w = w.reshape(B, S, H, hs)
    u = p["u"].astype(jnp.float32).reshape(H, hs)
    y, wkv_state = _wkv6_scan(r, k, v, w, u, wkv_state, single_step)
    # per-head group norm
    yf = y.reshape(B, S, H, hs)
    mean = yf.mean(-1, keepdims=True)
    var = yf.var(-1, keepdims=True)
    yf = (yf - mean) * jax.lax.rsqrt(var + 64e-5)
    yf = yf.reshape(B, S, d) * p["ln_w"].astype(jnp.float32) + p["ln_b"].astype(jnp.float32)
    out = dense((yf * g).astype(x.dtype), p["wo"])
    return out, x[:, -1], wkv_state


def _rwkv_channel_mix(cfg, p, x, shift_prev):
    xx = _token_shift(x, shift_prev)
    mu = p["mu"]
    xk = x + (xx - x) * jax.nn.sigmoid(mu[0].astype(jnp.float32)).astype(x.dtype)
    xr = x + (xx - x) * jax.nn.sigmoid(mu[1].astype(jnp.float32)).astype(x.dtype)
    k = jnp.square(jax.nn.relu(dense(xk, p["wk"]).astype(jnp.float32))).astype(x.dtype)
    r = jax.nn.sigmoid(dense(xr, p["wr"]).astype(jnp.float32)).astype(x.dtype)
    return r * dense(k, p["wv"]), x[:, -1]


def rwkv_fwd(cfg, p, x, norm_fn1, norm_fn2):
    """Full RWKV block (time mix + channel mix), training/prefill."""
    B = x.shape[0]
    st = rwkv_init_state(cfg, B, x.dtype)
    h, _, _ = _rwkv_time_mix(cfg, p["time"], norm_fn1(x), st["shift_t"], st["wkv"], False)
    x = x + h
    h, _ = _rwkv_channel_mix(cfg, p["channel"], norm_fn2(x), st["shift_c"])
    return x + h


def rwkv_prefill(cfg, p, x, norm_fn1, norm_fn2):
    B = x.shape[0]
    st = rwkv_init_state(cfg, B, x.dtype)
    n1 = norm_fn1(x)
    h, shift_t, wkv = _rwkv_time_mix(cfg, p["time"], n1, st["shift_t"], st["wkv"], False)
    x = x + h
    n2 = norm_fn2(x)
    h, shift_c = _rwkv_channel_mix(cfg, p["channel"], n2, st["shift_c"])
    return x + h, {"shift_t": shift_t, "shift_c": shift_c, "wkv": wkv}


def rwkv_decode(cfg, p, x, state, norm_fn1, norm_fn2):
    n1 = norm_fn1(x)
    h, shift_t, wkv = _rwkv_time_mix(
        cfg, p["time"], n1, state["shift_t"], state["wkv"], True
    )
    x = x + h
    n2 = norm_fn2(x)
    h, shift_c = _rwkv_channel_mix(cfg, p["channel"], n2, state["shift_c"])
    return x + h, {"shift_t": shift_t, "shift_c": shift_c, "wkv": wkv}
