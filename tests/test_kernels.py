"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes.  Every call passes ``interpret=True``
explicitly: the wrappers compile for the TPU unless told otherwise."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.optim.compression import quantize_int8

DTYPES = [jnp.float32, jnp.bfloat16]


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, Kh, Sq, Skv, D, causal, window)
    (1, 2, 2, 128, 128, 64, True, 0),
    (2, 4, 2, 128, 128, 64, True, 0),     # GQA 2:1
    (1, 4, 1, 256, 256, 32, True, 0),     # MQA
    (1, 2, 2, 128, 128, 64, False, 0),    # bidirectional (encoder)
    (1, 2, 2, 256, 256, 64, True, 64),    # sliding window
    (1, 2, 1, 64, 512, 64, True, 0),      # Sq != Skv
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_matches_ref(case, dtype):
    B, H, Kh, Sq, Skv, D, causal, window = case
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, Sq, D), dtype) / np.sqrt(D)
    k = jnp.asarray(rng.randn(B, Kh, Skv, D), dtype) / np.sqrt(D)
    v = jnp.asarray(rng.randn(B, Kh, Skv, D), dtype)
    q_offset = Skv - Sq if Sq != Skv else 0
    got = ops.flash_attention(q, k, v, causal, window, q_offset, True)
    want = ref.flash_attention_ref(q, k, v, causal, window, q_offset)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


def test_flash_attention_grad_matches_ref():
    rng = np.random.RandomState(1)
    B, H, Kh, S, D = 1, 2, 1, 128, 32
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) / np.sqrt(D)
    k = jnp.asarray(rng.randn(B, Kh, S, D), jnp.float32) / np.sqrt(D)
    v = jnp.asarray(rng.randn(B, Kh, S, D), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(jnp.tanh(ops.flash_attention(q, k, v, True, 0, 0, True)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.tanh(ref.flash_attention_ref(q, k, v, True, 0, 0)))

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# chunk_reduce (the Hoplite streaming accumulate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [17, 4096, 100_000])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("alpha", [1.0, 0.5])
def test_chunk_reduce_matches_ref(n, dtype, alpha):
    rng = np.random.RandomState(2)
    dst = jnp.asarray(rng.randn(n), dtype)
    src = jnp.asarray(rng.randn(n), dtype)
    got = ops.chunk_reduce(dst, src, alpha=alpha, interpret=True)
    want = ref.chunk_reduce_ref(dst, src, alpha=alpha)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("n", [300, 70_000])
def test_dequant_add_matches_ref(n):
    rng = np.random.RandomState(3)
    dst = jnp.asarray(rng.randn(n), jnp.float32)
    payload = jnp.asarray(rng.randn(n), jnp.float32)
    q, scale = quantize_int8(payload)
    got = ops.dequant_add(dst, q.reshape(-1), scale, interpret=True)
    want = ref.dequant_add_ref(dst, q.reshape(-1), scale, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 256), (1000, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_matches_ref(shape, dtype):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.randn(shape[-1]) * 0.1, dtype)
    got = ops.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )
