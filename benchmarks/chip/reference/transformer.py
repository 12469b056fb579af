"""Plain reference for the pre-LayerNorm transformers the benchmark trains:
decoder-only (StarCoder2) and encoder-decoder (Whisper).

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: full-softmax attention (queries in chunks only to
bound memory), per-layer rematerialization, next-token cross-entropy, and
AdamW as the traffic file states it.  It imports nothing of the program and
takes no weights from it: parameters come from ``weights.leaf_value`` and
batches from the benchmark's own ``data.host_batch``.  What it shares with
the program is the configuration file and the parameter paths, which
``param_spec`` writes down from the configuration alone.

Parameters are stored in the configuration's ``param_dtype`` between steps
(bfloat16 here), as the configuration states; every step computes in f32.
``precision="fp8"`` is the control: the same reference with every matmul
operand, and every gradient flowing back into one, rounded to scaled
float8_e4m3fn.
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

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn
LN_EPS = 1e-5
ATTN_CHUNK = 1024  # query rows per attention block
LOSS_CHUNK = 1024  # sequence positions per block of logits


def padded_vocab(program: dict) -> int:
    m = program.get("vocab_pad_to", 16)
    return -(-program["vocab_size"] // m) * m


def _layer_spec(program: dict, n: int, cross: bool) -> Dict[str, tuple]:
    d, f = program["d_model"], program["d_ff"]
    qd = program["num_heads"] * program["head_dim"]
    kvd = program["num_kv_heads"] * program["head_dim"]
    spec = {"ln1/w": (d,), "ln1/b": (d,),
            "attn/wq": (d, qd), "attn/wk": (d, kvd), "attn/wv": (d, kvd), "attn/wo": (qd, d)}
    if cross:
        spec.update({"ln_cross/w": (d,), "ln_cross/b": (d,),
                     "cross/wq": (d, qd), "cross/wk": (d, kvd), "cross/wv": (d, kvd),
                     "cross/wo": (qd, d)})
    spec.update({"ln2/w": (d,), "ln2/b": (d,), "ffn/wi": (d, f), "ffn/wo": (f, d)})
    return {k: (n,) + s for k, s in spec.items()}


def param_spec(program: dict) -> Dict[str, tuple]:
    """{parameter path: shape}, in the program's layout, from the configuration."""
    if program.get("norm") != "layernorm" or program.get("act") != "gelu":
        raise ValueError("this reference covers LayerNorm + GELU transformers only")
    d, V = program["d_model"], padded_vocab(program)
    enc = program.get("encoder_layers", 0)
    spec = {"embed": (V, d), "final_norm/w": (d,), "final_norm/b": (d,)}
    if not program.get("tie_embeddings", False):
        spec["lm_head"] = (d, V)
    for k, s in _layer_spec(program, program["num_layers"], cross=enc > 0).items():
        spec["stages/0/pos0/" + k] = s
    if enc:
        for k, s in _layer_spec(program, enc, cross=False).items():
            spec["encoder/stage/pos0/" + k] = s
        spec["encoder/final_norm/w"] = (d,)
        spec["encoder/final_norm/b"] = (d,)
    return spec


# ---------------------------------------------------------------------------
# precision: f32 (the reference) or scaled fp8 (the control)
# ---------------------------------------------------------------------------


def _fp8_round(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _fp8_round(x)


_fp8.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (_fp8_round(g),))


def _einsum_for(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def sinusoids(length: int, channels: int) -> np.ndarray:
    """Whisper's absolute position embedding (``sinusoids`` in its model.py)."""
    inc = math.log(10000) / (channels // 2 - 1)
    inv = np.exp(-inc * np.arange(channels // 2))
    t = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)], axis=1).astype(np.float32)


def _layer_norm(x, p, name):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * p[name + "/w"] + p[name + "/b"]


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x: (B, S, heads, D)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(ang))[:, None], jnp.asarray(np.sin(ang))[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _chunk(n: int, most: int) -> int:
    c = min(n, most)
    while n % c:
        c -= 1
    return c


def _attend(ein, q, k, v, causal: bool):
    """softmax(q k^T / sqrt(D)) v over whole keys, in blocks of query rows."""
    B, S, H, D = q.shape
    T = k.shape[1]
    c = _chunk(S, ATTN_CHUNK)
    qc = q.reshape(B, S // c, c, H, D).transpose(1, 0, 2, 3, 4)

    def block(args):
        qi, i = args
        s = ein("bqhd,bthd->bhqt", qi, k) / math.sqrt(D)
        if causal:
            qpos = i * c + jnp.arange(c)
            s = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        return ein("bhqt,bthd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(jax.checkpoint(block), (qc, jnp.arange(S // c)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def _attention(ein, program, p, name, x, src, causal, rope):
    B, S, _ = x.shape
    T = src.shape[1]
    H, K, D = program["num_heads"], program["num_kv_heads"], program["head_dim"]
    q = ein("bsd,de->bse", x, p[name + "/wq"]).reshape(B, S, H, D)
    k = ein("btd,de->bte", src, p[name + "/wk"]).reshape(B, T, K, D)
    v = ein("btd,de->bte", src, p[name + "/wv"]).reshape(B, T, K, D)
    if rope:
        q, k = _rope(q, program["rope_theta"]), _rope(k, program["rope_theta"])
    # grouped-query attention: query head h reads key/value head h // (H // K)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    out = _attend(ein, q, k, v, causal).reshape(B, S, H * D)
    return ein("bse,ed->bsd", out, p[name + "/wo"])


def _gelu(x):
    # the configuration runs GELU in its tanh form (``gelu_pytorch_tanh``)
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(ein, program, p, x, causal, rope, enc_out=None):
    h = _layer_norm(x, p, "ln1")
    x = x + _attention(ein, program, p, "attn", h, h, causal, rope)
    if enc_out is not None:
        h = _layer_norm(x, p, "ln_cross")
        x = x + _attention(ein, program, p, "cross", h, enc_out, False, False)
    h = _layer_norm(x, p, "ln2")
    return x + ein("bsf,fd->bsd", _gelu(ein("bsd,df->bsf", h, p["ffn/wi"])), p["ffn/wo"])


def _stack(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def _run_layers(layer, stacked, x):
    body = jax.checkpoint(lambda h, lp: (layer(lp, h), None))
    return lax.scan(body, x, stacked)[0]


def loss_fn(program: dict, ein, params: dict, batch: dict):
    """Mean next-token cross-entropy over the padded vocabulary, as run."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    d = program["d_model"]
    x = params["embed"][tokens]
    rope = program.get("rope") == "rope"
    enc_out = None
    if program.get("encoder_layers", 0):
        x = x + jnp.asarray(sinusoids(S, d))[None]
        frames = batch["encoder_frames"]
        h = frames + jnp.asarray(sinusoids(frames.shape[1], d))[None]
        h = _run_layers(
            lambda lp, h_: _layer(ein, program, lp, h_, causal=False, rope=False),
            _stack(params, "encoder/stage/pos0/"), h)
        enc_out = _layer_norm(h, params, "encoder/final_norm")
    x = _run_layers(
        lambda lp, h_: _layer(ein, program, lp, h_, causal=True, rope=rope, enc_out=enc_out),
        _stack(params, "stages/0/pos0/"), x)
    x = _layer_norm(x, params, "final_norm")
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    c = _chunk(S, LOSS_CHUNK)
    xs = x.reshape(B, S // c, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // c, c).transpose(1, 0, 2)

    def block(args):
        xi, li = args
        logits = ein("bsd,dv->bsv", xi, w)
        picked = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    return jnp.sum(lax.map(jax.checkpoint(block), (xs, ls))) / (B * S)


# ---------------------------------------------------------------------------
# three steps of AdamW, and the readings compared with the program's
# ---------------------------------------------------------------------------


def lr_at(opt: dict, count: int) -> float:
    """Learning rate of update ``count`` (1-based), as the traffic file states it."""
    warm = min(1.0, (count + 1) / max(1, opt["warmup_steps"]))
    prog = min(1.0, max(0.0, (count - opt["warmup_steps"])
                        / max(1, opt["total_steps"] - opt["warmup_steps"])))
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def _rows(batch: dict, n: int) -> dict:
    return {k: v[:n] for k, v in batch.items()}


@functools.lru_cache(maxsize=None)
def _programs(program_key: str, precision: str):
    program = json.loads(program_key)
    ein = _einsum_for(precision)
    dtype = jnp.dtype(program["param_dtype"])

    def loss32(params, batch):
        return loss_fn(program, ein, {k: v.astype(jnp.float32) for k, v in params.items()}, batch)

    def grads(params, batch):
        p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
        return jax.value_and_grad(lambda p: loss_fn(program, ein, p, batch))(p32)

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
    clipping.  ``fault`` plants a fault for calibration: ``"half"`` (loss
    and gradient over the first half of the rows) or ``"local:n"`` (the
    gradient over the first 1/n of the rows, as one of n chips would have
    without the exchange; the loss over all rows).
    """
    opt = traffic["adamw"]
    fns = _programs(json.dumps(program, sort_keys=True), precision)
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
            if fault == "half":
                batch = _rows(batch, B // 2)
            if fault and fault.startswith("local:"):
                loss = fns["loss"](params, batch)
                _, g = fns["grads"](params, _rows(batch, B // int(fault.split(":")[1])))
            else:
                loss, g = fns["grads"](params, batch)
            losses.append(float(loss))
            gn = {k: float(x) for k, x in fns["norms"](g).items()}
            if t == 1:
                grad_norms = gn
            gnorm = math.sqrt(sum(x * x for x in gn.values()))
            scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            hyper = (np.float32(scale), np.float32(lr_at(opt, t)), np.float32(opt["b1"]),
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
