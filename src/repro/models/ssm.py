"""State-space blocks: Mamba (Jamba's mixer), Mamba-2 (Granite 4.0-H's
mixer) and RWKV-6 ("Finch").

Mamba and RWKV-6 are linear-state recurrences implemented with ``lax.scan``
over time (the scan form is the correctness baseline and is what the
dry-run lowers).  Mamba-2 runs its scalar-decay recurrence as the chunked
SSD algorithm: matmuls within chunks and a scan over chunk states only.
Decode carries O(1) state per layer -- these are the architectures for
which long_500k is the showcase cell.

Shapes use (B, S, d) activations; state trees are dicts of arrays so the
serving engine can thread them generically like KV caches.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import Param, dense, rmsnorm


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
# Mamba-2 (SSD, arXiv:2405.21060), as in Granite 4.0-H
# ---------------------------------------------------------------------------


def mamba2_dims(cfg) -> Tuple[int, int, int, int, int]:
    """(heads H, head size P, groups G, state size N, conv channels H*P + 2*G*N)."""
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_dim
    return H, P, G, N, H * P + 2 * G * N


def mamba2_skel(cfg):
    d = cfg.d_model
    H, P, _, _, conv = mamba2_dims(cfg)
    di = H * P
    return {
        "in_proj": Param((d, di + conv + H), ("embed", None)),  # z | x B C | dt
        "conv_w": Param((cfg.ssm_conv_width, conv), (None, None), scale=0.5),
        "conv_b": Param((conv,), (None,), init="zeros"),
        "dt_bias": Param((H,), (None,), init="zeros"),
        "A_log": Param((H,), (None,), init="zeros"),
        "D": Param((H,), (None,), init="ones"),
        "norm": {"w": Param((di,), ("ssm",), init="ones")},
        "out_proj": Param((di, d), ("ssm", "embed")),
    }


def ssd(x, dt, A, B, C, chunk: int, state=None):
    """The recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t
    per head, computed in chunks of ``chunk`` positions (SSD, Mamba-2 §6).

    x (b, s, h, p); dt (b, s, h) f32, >= 0; A (h,) f32, < 0; B, C (b, s, g, n),
    group g serving heads g*h/g..; state (b, h, p, n) f32 or None (zeros).
    Returns y (b, s, h, p) f32 and the state after the last position.  The
    decays, their cumulative sums and the states are f32; x, B and C enter
    the matmuls in their own dtype, accumulated in f32.  A length that is
    not a multiple of ``chunk`` is padded with dt = 0, which leaves the
    state exactly as it was.
    """
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in (x, dt, B, C))
    c, l = (s + pad) // chunk, chunk
    xs = (x.astype(f32) * dt[..., None]).astype(x.dtype).reshape(b, c, l, g, h // g, p)
    Bs, Cs = B.reshape(b, c, l, g, n), C.reshape(b, c, l, g, n)
    cs = jnp.cumsum((dt * A).reshape(b, c, l, g, h // g), axis=2)  # log decay from the chunk's start
    # 1. within each chunk: y_i = sum_{j <= i} exp(cs_i - cs_j) (C_i . B_j) x_j
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cs, Bs, preferred_element_type=f32)
    seg = jnp.moveaxis(cs, 2, -1)  # (b, c, g, h/g, l)
    causal = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.exp(jnp.where(causal, seg[..., :, None] - seg[..., None, :], -jnp.inf))
    m = (decay * cb[:, :, :, None]).astype(x.dtype)
    y = jnp.einsum("bcghls,bcsghp->bclghp", m, xs, preferred_element_type=f32)
    # 2. each chunk's own state: sum_j exp(cs_last - cs_j) x_j B_j^T
    xw = (xs.astype(f32) * jnp.exp(cs[:, :, -1:] - cs)[..., None]).astype(x.dtype)
    own = jnp.einsum("bclghp,bclgn->bcghpn", xw, Bs, preferred_element_type=f32)

    # 3. the state entering each chunk, by a scan over the chunks
    def carry(S, inp):
        st, dec = inp
        return S * dec[..., None, None] + st, S

    S0 = jnp.zeros((b, g, h // g, p, n), f32) if state is None else state.reshape(b, g, h // g, p, n)
    final, entering = lax.scan(carry, S0, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0)))
    # 4. what the entering state gives: exp(cs_i) C_i . S
    y_in = jnp.einsum("bclgn,cbghpn->bclghp", Cs, entering, preferred_element_type=f32)
    y = y + y_in * jnp.exp(cs)[..., None]
    return y.reshape(b, c * l, h, p)[:, :s], final.reshape(b, h, p, n)


def _ssd_step(x, dt, A, B, C, state):
    """One position of the recurrence: x (b, h, p), dt (b, h), B, C (b, g, n)."""
    b, h, p = x.shape
    g = B.shape[1]
    rep = lambda t: jnp.repeat(t.astype(jnp.float32), h // g, axis=1)  # (b, h, n)
    dA = jnp.exp(dt * A)[..., None, None]
    state = dA * state + (dt[..., None] * x.astype(jnp.float32))[..., None] * rep(B)[:, :, None]
    return jnp.einsum("bhpn,bhn->bhp", state, rep(C)), state


def _causal_conv(x, w, b, prev):
    """Depthwise causal conv over time, in f32: x (B, S, C), w (W, C), prev
    (B, W-1, C) the inputs before x.  Returns (out, the last W-1 inputs)."""
    W, S = w.shape[0], x.shape[1]
    xpad = jnp.concatenate([prev.astype(x.dtype), x], axis=1).astype(jnp.float32)
    out = sum(xpad[:, i : i + S] * w[i].astype(jnp.float32) for i in range(W))
    return out + b.astype(jnp.float32), xpad[:, S:].astype(x.dtype)


def _mamba2_mix(cfg, p, h, conv_prev, state):
    """The mixer on the normed input h (B, S, d): in_proj, conv, SSD, gated
    norm, out_proj.  Returns (out, the conv's last inputs, the SSD state)."""
    with jax.named_scope("ssm"):
        H, P, G, N, conv = mamba2_dims(cfg)
        di = H * P
        Bsz, S = h.shape[:2]
        z, xbc, dt = jnp.split(dense(h, p["in_proj"]), [di, di + conv], axis=-1)
        xbc, conv_next = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_prev)
        x, Bm, Cm = jnp.split(jax.nn.silu(xbc).astype(h.dtype), [di, di + G * N], axis=-1)
        x = x.reshape(Bsz, S, H, P)
        Bm, Cm = Bm.reshape(Bsz, S, G, N), Cm.reshape(Bsz, S, G, N)
        delta = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        if S == 1 and state is not None:
            y, state = _ssd_step(x[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0], state)
            y = y[:, None]
        else:
            with jax.named_scope("ssd"):
                y, state = ssd(x, delta, A, Bm, Cm, cfg.ssm_chunk, state)
        y = y + x.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
        y = y.reshape(Bsz, S, di) * jax.nn.silu(z.astype(jnp.float32))
        eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
        y = rmsnorm(y, p["norm"]["w"], eps=eps, offset=0.0).astype(h.dtype)
        return dense(y, p["out_proj"]), conv_next, state


def mamba2_init_state(cfg, batch: int, dtype=jnp.float32):
    H, P, _, N, conv = mamba2_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv), dtype),
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def mamba2_fwd(cfg, p, x):
    """Training forward (fresh state)."""
    out, _, _ = _mamba2_mix(cfg, p, x, mamba2_init_state(cfg, x.shape[0], x.dtype)["conv"], None)
    return out


def mamba2_prefill(cfg, p, x):
    """Forward returning the state for subsequent decode."""
    out, conv, ssm = _mamba2_mix(cfg, p, x, mamba2_init_state(cfg, x.shape[0], x.dtype)["conv"], None)
    return out, {"conv": conv, "ssm": ssm}


def mamba2_decode(cfg, p, x, state: Dict[str, jax.Array]):
    out, conv, ssm = _mamba2_mix(cfg, p, x, state["conv"], state["ssm"])
    return out, {"conv": conv, "ssm": ssm}


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
