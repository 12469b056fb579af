"""Plain reference for the Granite 4.0-H hybrid: Mamba-2 mixers and GQA
attention in one stack, each followed by a SwiGLU MLP.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``.  The Mamba-2 mixer runs its state recurrence one
position at a time, per head h,

    S_t = exp(dt_{t,h} A_h) S_{t-1} + dt_{t,h} x_{t,h} (x) B_t,    y_{t,h} = S_t C_t + D_h x_{t,h},

never the chunked SSD algorithm; the scan over positions is checkpointed in
blocks of ``SCAN_BLOCK`` steps so that its backward fits at 4096 positions.
Around it: ``[z | xBC | dt] = u W_in``, a causal depthwise conv of width 4
with bias, SiLU, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the
gated norm ``rmsnorm(y * silu(z)) * w`` and ``W_out``.  Attention has no
positional encoding and scores q.k times ``attention_scale``.  The
residuals are ``h += r * mixer(norm(h))`` and ``h += r * mlp(norm(h))``, the
embedding is multiplied by ``embedding_multiplier`` and the tied logits
divided by ``logits_divisor``; RMSNorm scales by its weight.  Parameters
come from ``weights.leaf_value`` and batches from ``data.host_batch``; the
attention-free helpers, the fp8 control and AdamW's schedule are those of
``transformer.py``.  It imports nothing of the program.

``fault="chunk_reset"`` plants the fault the limits are checked against:
the recurrence starts from a zero state at every multiple of
``ssm_chunk``, as the SSD would with the state between chunks dropped.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import data, weights
from benchmarks.chip.reference import transformer as tf

SCAN_BLOCK = 64  # positions per checkpointed block of the Mamba-2 recurrence


def _mamba_dims(program: dict):
    H, P, G, N = (program[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state_dim"))
    return H, P, G, N, H * P + 2 * G * N


def param_spec(program: dict) -> Dict[str, tuple]:
    """{parameter path: shape}, in the program's layout, from the configuration."""
    if program.get("norm") != "rmsnorm_plain" or program.get("act") != "swiglu":
        raise ValueError("this reference covers RMSNorm (scale w) + SwiGLU hybrids only")
    d, f, V = program["d_model"], program["d_ff"], tf.padded_vocab(program)
    spec = {"embed": (V, d), "final_norm/w": (d,)}
    if not program.get("tie_embeddings", False):
        spec["lm_head"] = (d, V)
    pattern = program["pattern"]
    n = program["num_layers"] // len(pattern)
    for i, layer in enumerate(pattern):
        s = {"ln1/w": (d,), "ln2/w": (d,), "ffn/wi": (d, f), "ffn/wg": (d, f), "ffn/wo": (f, d)}
        if layer["kind"] == "attn":
            qd = program["num_heads"] * program["head_dim"]
            kvd = program["num_kv_heads"] * program["head_dim"]
            s.update({"attn/wq": (d, qd), "attn/wk": (d, kvd), "attn/wv": (d, kvd), "attn/wo": (qd, d)})
        elif layer["kind"] == "mamba2":
            H, P, _, _, conv = _mamba_dims(program)
            s.update({"mixer/in_proj": (d, H * P + conv + H),
                      "mixer/conv_w": (program["ssm_conv_width"], conv), "mixer/conv_b": (conv,),
                      "mixer/dt_bias": (H,), "mixer/A_log": (H,), "mixer/D": (H,),
                      "mixer/norm/w": (H * P,), "mixer/out_proj": (H * P, d)})
        else:
            raise ValueError(f"this reference has no layer kind {layer['kind']!r}")
        spec.update({f"stages/0/pos{i}/{k}": (n,) + v for k, v in s.items()})
    return spec


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _attention(ein, program, p, x):
    """Causal GQA softmax attention over whole keys, in blocks of query rows."""
    B, S, _ = x.shape
    H, K, D = program["num_heads"], program["num_kv_heads"], program["head_dim"]
    q = ein("bsd,de->bse", x, p["attn/wq"]).reshape(B, S, H, D)
    k = ein("bsd,de->bse", x, p["attn/wk"]).reshape(B, S, K, D)
    v = ein("bsd,de->bse", x, p["attn/wv"]).reshape(B, S, K, D)
    # grouped-query attention: query head h reads key/value head h // (H // K)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    c = tf._chunk(S, tf.ATTN_CHUNK)
    qc = q.reshape(B, S // c, c, H, D).transpose(1, 0, 2, 3, 4)

    def block(args):
        qi, i = args
        s = ein("bqhd,bthd->bhqt", qi, k) * program["attention_scale"]
        qpos = i * c + jnp.arange(c)
        s = jnp.where(qpos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return ein("bhqt,bthd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(jax.checkpoint(block), (qc, jnp.arange(S // c)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H * D)
    return ein("bse,ed->bsd", out, p["attn/wo"])


def _recurrence(ein, x, dt, A, B, C, reset_every: int):
    """y_t = S_t C_t with S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, one
    position at a time.  x (b, s, h, p); dt (b, s, h); A (h,); B, C
    (b, s, h, n).  ``reset_every`` > 0 zeroes the state at its multiples."""
    b, S, H, P = x.shape
    blk = tf._chunk(S, SCAN_BLOCK)

    def step(state, inp):
        xt, dtt, Bt, Ct, t = inp
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return state, ein("bhpn,bhn->bhp", state, Ct)

    def blocks(t):  # (b, S, ...) -> (S / blk, blk, b, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((S // blk, blk) + t.shape[1:])

    xs = tuple(blocks(t) for t in (x, dt, B, C)) + (jnp.arange(S).reshape(S // blk, blk),)
    block = jax.checkpoint(lambda state, xb: lax.scan(step, state, xb))
    _, ys = lax.scan(block, jnp.zeros((b, H, P, B.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(ys.reshape((S, b, H, P)), 0, 1)


def _mamba2(ein, program, p, u, fault):
    H, P, G, N, conv = _mamba_dims(program)
    di = H * P
    b, S, _ = u.shape
    zxbcdt = ein("bsd,de->bse", u, p["mixer/in_proj"])
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:di + conv], zxbcdt[..., di + conv:]
    W = program["ssm_conv_width"]
    xpad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(xpad[:, k:k + S] * p["mixer/conv_w"][k] for k in range(W)) + p["mixer/conv_b"]
    xbc = _silu(xbc)
    x = xbc[..., :di].reshape(b, S, H, P)
    # group g's B and C serve heads g * H / G .. (g + 1) * H / G - 1
    Bh = jnp.repeat(xbc[..., di:di + G * N].reshape(b, S, G, N), H // G, axis=2)
    Ch = jnp.repeat(xbc[..., di + G * N:].reshape(b, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["mixer/dt_bias"])
    A = -jnp.exp(p["mixer/A_log"])
    reset = program["ssm_chunk"] if fault == "chunk_reset" else 0
    y = _recurrence(ein, x, dt, A, Bh, Ch, reset) + p["mixer/D"][:, None] * x
    y = _rmsnorm(y.reshape(b, S, di) * _silu(z), p["mixer/norm/w"], program["norm_eps"])
    return ein("bse,ed->bsd", y, p["mixer/out_proj"])


def _layer(ein, program, kind, p, x, fault):
    r, eps = program["residual_multiplier"], program["norm_eps"]
    h = _rmsnorm(x, p["ln1/w"], eps)
    mix = _attention(ein, program, p, h) if kind == "attn" else _mamba2(ein, program, p, h, fault)
    x = x + r * mix
    h = _rmsnorm(x, p["ln2/w"], eps)
    g = ein("bsd,df->bsf", h, p["ffn/wg"])
    return x + r * ein("bsf,fd->bsd", _silu(g) * ein("bsd,df->bsf", h, p["ffn/wi"]), p["ffn/wo"])


def loss_fn(program: dict, ein, params: dict, batch: dict, fault: Optional[str] = None):
    """Mean next-token cross-entropy over the padded vocabulary, as run."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    d = program["d_model"]
    x = params["embed"][tokens] * program["embedding_multiplier"]
    pattern = program["pattern"]
    stacked = [tf._stack(params, f"stages/0/pos{i}/") for i in range(len(pattern))]
    layers = [jax.checkpoint(functools.partial(_layer, ein, program, spec["kind"], fault=fault))
              for spec in pattern]

    def block(h, lps):
        for layer, lp in zip(layers, lps):
            h = layer(lp, h)
        return h, None

    x = lax.scan(block, x, stacked)[0]
    x = _rmsnorm(x, params["final_norm/w"], program["norm_eps"])
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    c = tf._chunk(S, tf.LOSS_CHUNK)
    xs = x.reshape(B, S // c, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // c, c).transpose(1, 0, 2)

    def loss_block(args):
        xi, li = args
        logits = ein("bsd,dv->bsv", xi, w) / program["logits_divisor"]
        picked = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.sum(lax.map(jax.checkpoint(loss_block), (xs, ls))) / (B * S)


# ---------------------------------------------------------------------------
# AdamW steps, and the readings compared with the program's
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(program_key: str, precision: str, fault: Optional[str]):
    program = json.loads(program_key)
    ein = tf._einsum_for(precision)
    dtype = jnp.dtype(program["param_dtype"])
    model_fault = fault if fault == "chunk_reset" else None

    def to32(params):
        return {k: v.astype(jnp.float32) for k, v in params.items()}

    def loss32(params, batch):
        return loss_fn(program, ein, to32(params), batch, model_fault)

    def grads(params, batch):
        return jax.value_and_grad(lambda p: loss_fn(program, ein, p, batch, model_fault))(to32(params))

    def update(p, g, m, v, scale, lr, b1, b2, eps, wd, count):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** count)
        vhat = v / (1 - b2 ** count)
        p32 = p.astype(jnp.float32)
        return (p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p32)).astype(dtype), m, v

    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for k, x in tree.items()}

    def change_norms(key, params):
        return {k: jnp.linalg.norm(
            (p.astype(jnp.float32) - weights.leaf_value(key, k, p.shape, p.dtype).astype(jnp.float32)
             ).ravel()) for k, p in params.items()}

    def init(key, shapes):
        return {k: weights.leaf_value(key, k, s, dtype) for k, s in shapes}

    return {
        "loss": jax.jit(loss32),
        "grads": jax.jit(grads),
        "update": jax.jit(update, donate_argnums=(0, 2, 3)),
        "norms": jax.jit(norms),
        "change_norms": jax.jit(change_norms),
        "init": jax.jit(init, static_argnums=1),
    }


def run(program: dict, traffic: dict, seed: int, device, steps: int = 3,
        precision: str = "f32", fault: Optional[str] = None) -> dict:
    """``steps`` AdamW steps from the seeded weights on the seeded batches.

    Returns ``{"losses": [...], "grad_norms": {path: |g|}, "change_norms":
    {path: |p_steps - p_0|}}``; ``grad_norms`` is step 1's gradient before
    clipping.  ``fault``: ``"half"`` (loss and gradient over the first half
    of the rows, or of a single row's positions), ``"local:n"`` (the gradient over the first 1/n of the
    rows), or ``"chunk_reset"`` (the state dropped between SSD chunks).
    """
    opt = traffic["adamw"]
    fns = _programs(json.dumps(program, sort_keys=True), precision, fault)
    key = weights.base_key(seed)
    shapes = tuple(sorted(param_spec(program).items()))
    with jax.default_device(device):
        params = fns["init"](key, shapes)
        m_host: Dict[str, np.ndarray] = {}
        v_host: Dict[str, np.ndarray] = {}
        losses, grad_norms = [], {}
        for t in range(1, steps + 1):
            batch = {k: jnp.asarray(v) for k, v in
                     data.host_batch(program, traffic, t - 1, seed).items()}
            B = batch["tokens"].shape[0]
            if fault == "half":  # of a single row, its first half of the positions
                batch = (tf._rows(batch, B // 2) if B > 1 else
                         {k: v[:, : v.shape[1] // 2] for k, v in batch.items()})
            if fault and fault.startswith("local:"):
                loss = fns["loss"](params, batch)
                _, g = fns["grads"](params, tf._rows(batch, B // int(fault.split(":")[1])))
            else:
                loss, g = fns["grads"](params, batch)
            losses.append(float(loss))
            gn = {k: float(x) for k, x in fns["norms"](g).items()}
            if t == 1:
                grad_norms = gn
            gnorm = math.sqrt(sum(x * x for x in gn.values()))
            scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            hyper = (np.float32(scale), np.float32(tf.lr_at(opt, t)), np.float32(opt["b1"]),
                     np.float32(opt["b2"]), np.float32(opt["eps"]),
                     np.float32(opt["weight_decay"]), np.float32(t))
            for k in sorted(params):
                gk = g.pop(k)
                if t == 1:
                    m = jnp.zeros(gk.shape, jnp.float32)
                    v = jnp.zeros(gk.shape, jnp.float32)
                else:
                    m, v = jnp.asarray(m_host.pop(k)), jnp.asarray(v_host.pop(k))
                params[k], m, v = fns["update"](params[k], gk, m, v, *hyper)
                if t < steps:
                    m_host[k], v_host[k] = np.asarray(m), np.asarray(v)
                del gk, m, v
        change = {k: float(x) for k, x in fns["change_norms"](key, params).items()}
        del params
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
