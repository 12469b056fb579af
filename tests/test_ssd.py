"""Mamba-2's chunked SSD against its recurrence, and Granite's scales and
multipliers against hand computations, on the CPU at small sizes.

The SSD (``models/ssm.ssd``) must equal the recurrence run one position at
a time (``_ssd_step``), values and gradients, also across a padded last
chunk and with decays close to 1, where the state carried between chunks
makes most of the result."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import moe
from repro.models import transformer as T
from repro.models.common import init_params, rmsnorm
from repro.models.ssm import _ssd_step, ssd

B, S, H, P, G, N, CHUNK = 2, 20, 4, 3, 2, 5, 8  # 20 positions: 2.5 chunks of 8


def inputs(seed=0, slow=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H))) * (0.01 if slow else 1.0)
    A = -jax.nn.sigmoid(jax.random.normal(k[2], (H,))) * (0.3 if slow else 3.0)
    Bm = jax.random.normal(k[3], (B, S, G, N))
    Cm = jax.random.normal(k[4], (B, S, G, N))
    return x, dt, A, Bm, Cm, jax.random.normal(k[5], (B, H, P, N))


def recurrence(x, dt, A, Bm, Cm, state=None):
    state = jnp.zeros((B, H, P, N)) if state is None else state
    ys = []
    for t in range(x.shape[1]):
        y, state = _ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_matches_the_recurrence(with_state):
    x, dt, A, Bm, Cm, s0 = inputs()
    s0 = s0 if with_state else None
    assert float(jnp.exp(dt * A).min()) >= 0.99  # the state outlives the chunks
    y, state = ssd(x, dt, A, Bm, Cm, CHUNK, s0)
    y_ref, state_ref = recurrence(x, dt, A, Bm, Cm, s0)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    # the pad positions of the last chunk leave the state as the 20th left it
    np.testing.assert_allclose(state, state_ref, rtol=1e-5, atol=1e-5)
    # and the chunks' own states alone would not do: the carried state matters
    y_cut, _ = ssd(x[:, 16:], dt[:, 16:], A, Bm[:, 16:], Cm[:, 16:], CHUNK)
    assert float(jnp.abs(y_cut - y[:, 16:]).max()) > 0.1


def test_ssd_gradients_match_the_recurrence():
    x, dt, A, Bm, Cm, s0 = inputs(seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P))

    def loss(fn):
        def f(*a):
            y, state = fn(*a)
            return jnp.sum(y * w) + jnp.sum(state)
        return jax.grad(f, argnums=tuple(range(6)))

    got = loss(lambda *a: ssd(*a[:5], CHUNK, a[5]))(x, dt, A, Bm, Cm, s0)
    want = loss(recurrence)(x, dt, A, Bm, Cm, s0)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-4)


def test_ssd_with_fast_decay_matches_the_recurrence():
    x, dt, A, Bm, Cm, _ = inputs(seed=2, slow=False)
    y, state = ssd(x, dt, A, Bm, Cm, CHUNK)
    y_ref, state_ref = recurrence(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, state_ref, rtol=1e-5, atol=1e-5)


def test_attention_scale_by_hand():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k[0], (1, 2, 2, 12, 16))  # (B, K, G, S, D)
    kk = jax.random.normal(k[1], (1, 2, 12, 16))
    v = jax.random.normal(k[2], (1, 2, 12, 16))
    pos = jnp.arange(12)
    out = attn.flash_ref(q, kk, v, pos, pos, True, 0, 0.015625)
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, kk) * 0.015625
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    want = jnp.einsum("bkgqs,bksd->bkgqd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    # the default stays 1/sqrt(D)
    np.testing.assert_allclose(attn.flash_ref(q, kk, v, pos, pos, True),
                               attn.flash_ref(q, kk, v, pos, pos, True, 0, 0.25), rtol=1e-6)
    # and decode scales the same way
    got = attn.decode_attend(q[:, :, :, -1:], jnp.moveaxis(kk, 1, 2), jnp.moveaxis(v, 1, 2), pos,
                             jnp.int32(11), scale=0.015625)
    np.testing.assert_allclose(got, want[:, :, :, -1:], rtol=1e-5, atol=1e-5)


def test_rmsnorm_plain_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 8))
    w = jax.random.normal(jax.random.PRNGKey(5), (8,))
    want = x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(rmsnorm(x, w, eps=1e-5, offset=0.0), want, rtol=1e-5)
    np.testing.assert_allclose(rmsnorm(x, w - 1.0, eps=1e-5), want, rtol=1e-5)


def test_multipliers_by_hand():
    """One attention layer: h = 12 e; h += 0.22 attn(norm h); h += 0.22 mlp(norm h);
    logits = norm(h) e^T / 8."""
    cfg = ModelConfig(name="t", family="hybrid", num_layers=1, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=64, vocab_size=64, head_dim=8,
                      pattern=(LayerSpec(kind="attn"),), rope="none", norm="rmsnorm_plain",
                      norm_eps=1e-5, tie_embeddings=True, dtype="float32", param_dtype="float32",
                      embedding_multiplier=12.0, residual_multiplier=0.22, logits_divisor=8.0,
                      attention_scale=0.015625)
    params = init_params(T.model_skel(cfg), jax.random.PRNGKey(6), dtype_override=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 10), 0, 64)
    logits, _ = T.forward(cfg, params, {"tokens": tokens})
    lp = jax.tree.map(lambda a: a[0], params["stages"][0]["pos0"])
    norm = lambda w, h: rmsnorm(h, w, eps=1e-5, offset=0.0)
    pos = jnp.arange(10)
    h = params["embed"][tokens] * 12.0
    h = h + 0.22 * attn.attention_fwd(cfg, lp["attn"], norm(lp["ln1"]["w"], h), cfg.pattern[0], pos)
    h = h + 0.22 * moe.ffn_fwd(cfg, lp["ffn"], norm(lp["ln2"]["w"], h))
    want = norm(params["final_norm"]["w"], h) @ params["embed"].T / 8.0
    np.testing.assert_allclose(logits, want, rtol=2e-5, atol=2e-5)
    # and the multipliers are what moves it from the plain model
    plain = dataclasses.replace(cfg, embedding_multiplier=1.0, residual_multiplier=1.0,
                                logits_divisor=1.0)
    assert not np.allclose(T.forward(plain, params, {"tokens": tokens})[0], logits)


def test_granite_config_has_the_published_period():
    cfg = ARCHS["granite-4.0-h-micro"]
    kinds = [s.kind for s in cfg.pattern] * cfg.num_blocks
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert cfg.ssm_heads * cfg.ssm_head_dim == cfg.ssm_expand * cfg.d_model
    assert 3.1e9 < cfg.param_count() < 3.3e9  # "3B" (granite-4.0-h-micro)
