"""Block plan of the streaming-softmax attention, and its padded path.

`block_plan` keeps a length's divisor block where that block is at least
128 or the whole length, and otherwise pads the length to a lane-aligned
block.  The padded path (whisper's 1500 frames, other lengths with no good
divisor) must give the dense softmax's output and gradients.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import block_plan, flash_ref


@pytest.mark.parametrize("sq", [1500, 448])
def test_whisper_frames_run_in_512_key_blocks(sq):
    qb, kb, sq_pad, skv_pad = block_plan(sq, 1500)
    assert (qb, kb, sq_pad, skv_pad) == (sq, 512, sq, 1536)
    assert (skv_pad - 1500) * 8 <= skv_pad


@pytest.mark.parametrize(
    "sq, skv, blocks",
    [
        (448, 448, (448, 448)),
        (4096, 4096, (2048, 1024)),
        (32, 32, (32, 32)),
        (2, 2, (2, 2)),
    ],
)
def test_lengths_with_good_divisors_keep_their_blocks(sq, skv, blocks):
    assert block_plan(sq, skv) == (*blocks, sq, skv)


@pytest.mark.parametrize("n", [1025, 1100, 1500, 2049, 2100, 3000, 4097, 9999])
def test_engaged_blocks_are_lane_aligned_and_pad_at_most_an_eighth(n):
    qb, kb, sq_pad, skv_pad = block_plan(n, n)
    for b, padded in ((qb, sq_pad), (kb, skv_pad)):
        assert padded % b == 0 and n <= padded
        if padded != n:
            assert b % 128 == 0 and (padded - n) * 8 <= padded


def _dense(q, k, v, q_pos, kv_pos, causal, window):
    """Plain f32 softmax attention over (B, K, G, S, D) heads."""
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, k, precision="highest") / math.sqrt(q.shape[-1])
    mask = jnp.ones((q_pos.size, kv_pos.size), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= (q_pos[:, None] - kv_pos[None, :]) < window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bksd->bkgqd", p, v, precision="highest")


@pytest.mark.parametrize(
    "sq, skv, causal, window",
    [
        (1500, 1500, False, 0),  # whisper's encoder self-attention
        (24, 1500, False, 0),  # cross-attention over 1500 frames
        (1100, 1100, True, 0),  # causal self-attention, 1100 -> 1152
        (1100, 1100, True, 300),  # windowed
        (2100, 40, False, 0),  # query rows padded, 2100 -> 2304
    ],
)
def test_padded_path_matches_dense_softmax(sq, skv, causal, window):
    *_, sq_pad, skv_pad = block_plan(sq, skv)
    assert (sq_pad, skv_pad) != (sq, skv)  # the padded path runs
    B, K, G, D = 1, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(sq + skv), 4)
    q = jax.random.normal(ks[0], (B, K, G, sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, K, skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, K, skv, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, K, G, sq, D), jnp.float32)
    q_pos = jnp.arange(sq, dtype=jnp.int32)
    kv_pos = jnp.arange(skv, dtype=jnp.int32)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v, q_pos, kv_pos, causal, window)
            return jnp.sum(out * ct), out

        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    out, grads = run(flash_ref)
    want, want_grads = run(_dense)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, g, w in zip("qkv", grads, want_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5, err_msg=name)
