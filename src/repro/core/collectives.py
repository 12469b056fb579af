"""TPU-native Hoplite collectives: chunk-pipelined chain/tree schedules.

This is the hardware adaptation of the paper's data plane.  On TPU the
only inter-chip data path is an XLA collective, so Hoplite's transfer
schedules are expressed as explicit ``jax.lax.ppermute`` programs inside
``shard_map``:

  * ``chain_allreduce``    -- the paper's allreduce (reduce chain into the
    last rank, then broadcast chain back), *fused*: chunk k starts its
    broadcast leg while chunk k+1 is still reducing.  This is precisely
    section 4.2's "reduce followed by broadcast ... streamed end to end",
    and with C chunks costs (C + 2n - 3) steps of S/C bytes each
    ~= 2 S/B + 2 n (L + (S/C)/B)  -- bandwidth-competitive with ring
    allreduce while keeping the paper's reduce->broadcast structure.
    Chunks are whole (rows, 128) tiles, so a step moves its chunk alone.
  * ``chain_reduce`` / ``chain_broadcast`` -- the unfused building blocks
    (Get/Reduce composition), also chunk-pipelined.
  * ``two_level_allreduce`` -- the paper's 2-D sqrt(n) chain: reduce within
    groups, chain across group roots, broadcast back.
  * ``binomial_broadcast`` -- the MPI-style static tree, kept as a baseline
    (and used where a true one-to-all of a *replicated-source* is needed).
  * ``ring_reduce_scatter`` / ``ring_all_gather`` / ``rs_ag_allreduce`` --
    beyond-paper, bandwidth-optimal ring forms.
  * ``hoplite_psum`` -- the dispatcher: tiny tensors go straight to
    ``lax.psum`` (the TPU analogue of the <64 KB directory-inline fast
    path); large tensors pick 1-D vs 2-D chains via nBL > S.
  * ``grad_sync`` -- the trainer's gradient sync over one axis.

How many chunks an object is cut into, and which schedule moves it, is
planned in ``core/planner.py`` (``CollectiveConfig``); this module holds
only the programs.  All functions assume they run inside ``shard_map``
with ``axis_name`` available, and operate on the *local* shard.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.planner import (
    ICI_CONFIG,
    CollectiveConfig,
    autotune_num_chunks,
    two_level_group_sizes,
)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


_LANES = 128


def _to_chunks(x: jax.Array, num_chunks: int):
    """Flatten and pad x to (num_chunks, rows, _LANES).

    ``rows`` is a multiple of the dtype's packed sublane count (8 rows of
    32-bit, 16 of bf16, 32 of 8-bit), so a chunk is whole (rows, 128)
    tiles and the chunk index is the untiled leading axis: slicing,
    updating and ppermuting one chunk touches that chunk alone, in the
    buffer's own layout.  The padding, fewer than ``unit * 128`` zeros
    per chunk, is sliced off by ``_from_chunks``."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    unit = max(8, 32 // x.dtype.itemsize)
    rows = -(-n // (num_chunks * _LANES))
    rows = -(-rows // unit) * unit
    pad = num_chunks * rows * _LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(num_chunks, rows, _LANES), n


def _from_chunks(chunks: jax.Array, orig_elems: int, shape, dtype):
    return chunks.reshape(-1)[:orig_elems].reshape(shape).astype(dtype)


def _dyn_chunk(chunks: jax.Array, k):
    k = jnp.clip(k, 0, chunks.shape[0] - 1)
    return lax.dynamic_index_in_dim(chunks, k, axis=0, keepdims=False)


def _set_chunk(chunks: jax.Array, k, val):
    k = jnp.clip(k, 0, chunks.shape[0] - 1)
    return lax.dynamic_update_index_in_dim(chunks, val, k, axis=0)


def _add_chunk(chunks: jax.Array, k, val):
    cur = _dyn_chunk(chunks, k)
    return _set_chunk(chunks, k, cur + val)


def _receive(buf, k, active, recv, add: bool):
    """The receiving half of a pipelined chain step over ``buf``
    (C, rows, 128): where ``active`` and ``k`` is in [0, C), add
    (``add``) or write the chunk ``recv`` into chunk ``k``."""
    ok = active & (k >= 0) & (k < buf.shape[0])
    if add:
        return _add_chunk(buf, k, jnp.where(ok, recv, 0).astype(buf.dtype))
    cur = _dyn_chunk(buf, k)
    return _set_chunk(buf, k, jnp.where(ok, recv, cur))


def _chain_pass(buf, axis_name: str, perm, pos, active, steps: int, add: bool):
    """A pipelined chain pass of ``steps`` (C + chain length - 2) steps:
    at step t the rank ``pos`` hops from the chain's head sends chunk
    ``t - pos`` along ``perm`` and receives chunk ``t - pos + 1``."""

    def step(t, buf):
        k_send = t - pos
        recv = lax.ppermute(_dyn_chunk(buf, k_send), axis_name, perm)
        return _receive(buf, k_send + 1, active, recv, add)

    return lax.fori_loop(0, steps, step, buf)


def _up(n: int):
    """ppermute pairs of a chain over ranks 0..n-1, each to the next."""
    return [(i, i + 1) for i in range(n - 1)]


def _flip(perm):
    return [(dst, src) for src, dst in perm]


# ---------------------------------------------------------------------------
# fused chain allreduce (the paper's reduce->broadcast, streamed)
# ---------------------------------------------------------------------------


def pairwise_exchange_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """n == 2 degenerate chain: one bidirectional exchange.

    For two ranks the 1-D chain IS a pairwise exchange (send-all /
    receive-all on full-duplex links), and it needs NO flat reshape:
    under partial-manual shard_map a reshape of a tensor that is still
    sharded over the auto (data/model) axes can force GSPMD to replicate
    it."""
    peer = lax.ppermute(x, axis_name, [(0, 1), (1, 0)])
    return x + peer


def chain_allreduce(
    x: jax.Array,
    axis_name: str,
    num_chunks: Optional[int] = None,
) -> jax.Array:
    """Hoplite allreduce: pipelined chain-reduce into rank n-1 overlapped
    with a pipelined chain-broadcast back toward rank 0.

    Chunk k is fully reduced at rank n-1 at step k+n-2 and immediately
    begins its broadcast leg at step k+n-1 -- the broadcast of chunk k
    overlaps the reduction of chunks k+1..  (paper sections 4.2/4.3).

    ``num_chunks=None`` autotunes C from the Appendix-A cost model.
    """
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    if n == 2:
        return pairwise_exchange_allreduce(x, axis_name)
    idx = lax.axis_index(axis_name)
    C = num_chunks or autotune_num_chunks(n, x.size * x.dtype.itemsize)
    acc, orig = _to_chunks(x, C)  # partial-sum buffer (reduce direction)
    fin = jnp.zeros_like(acc)  # final-value buffer (broadcast direction)
    perm_up = _up(n)
    perm_down = _flip(perm_up)

    def body(t, carry):
        acc, fin = carry
        # reduce leg: i sends acc[t-i] to i+1, which accumulates
        recv = lax.ppermute(_dyn_chunk(acc, t - idx), axis_name, perm_up)
        acc = _receive(acc, t - idx + 1, idx >= 1, recv, add=True)
        # broadcast leg, n-1 steps behind: i sends final[t - 2(n-1) + i] to
        # i-1; rank n-1's final values are its acc, this step's sum included
        k = t - 2 * (n - 1) + idx
        final = jnp.where(idx == n - 1, _dyn_chunk(acc, k), _dyn_chunk(fin, k))
        recv = lax.ppermute(final, axis_name, perm_down)
        fin = _receive(fin, t - 2 * (n - 1) + idx + 1, idx <= n - 2, recv, add=False)
        return acc, fin

    acc, fin = lax.fori_loop(0, C + 2 * n - 3, body, (acc, fin))
    out = jnp.where(idx == n - 1, acc, fin)
    return _from_chunks(out, orig, x.shape, x.dtype)


# ---------------------------------------------------------------------------
# unfused building blocks
# ---------------------------------------------------------------------------


def chain_reduce(
    x: jax.Array, axis_name: str, num_chunks: Optional[int] = None
) -> jax.Array:
    """Pipelined 1-D chain reduce into rank n-1 (others return partials)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    C = num_chunks or autotune_num_chunks(n, x.size * x.dtype.itemsize)
    acc, orig = _to_chunks(x, C)
    acc = _chain_pass(acc, axis_name, _up(n), idx, idx >= 1, C + n - 2, add=True)
    return _from_chunks(acc, orig, x.shape, x.dtype)


def chain_broadcast(
    x: jax.Array, axis_name: str, num_chunks: Optional[int] = None, root: str = "last"
) -> jax.Array:
    """Pipelined chain broadcast from rank n-1 (or 0) through every rank."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    C = num_chunks or autotune_num_chunks(n, x.size * x.dtype.itemsize)
    buf, orig = _to_chunks(x, C)
    if root == "last":
        perm, pos = _flip(_up(n)), (n - 1) - idx  # hops from root
    else:
        perm, pos = _up(n), idx
    buf = _chain_pass(buf, axis_name, perm, pos, pos >= 1, C + n - 2, add=False)
    return _from_chunks(buf, orig, x.shape, x.dtype)


def binomial_broadcast(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """MPI-style static binomial tree broadcast (log2 n rounds, store &
    forward).  A baseline for the chain broadcast."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    # rotate so root behaves as rank 0
    vidx = (idx - root) % n
    rounds = max(1, math.ceil(math.log2(n)))
    for r in range(rounds):
        span = 1 << r
        perm = [((i + root) % n, (i + span + root) % n) for i in range(span) if i + span < n]
        recv = lax.ppermute(x, axis_name, perm)
        is_recv = (vidx >= span) & (vidx < 2 * span)
        x = jnp.where(is_recv, recv, x)
    return x


# ---------------------------------------------------------------------------
# two-level (2-D sqrt-n) chain allreduce
# ---------------------------------------------------------------------------


def two_level_allreduce(
    x: jax.Array,
    axis_name: str,
    num_chunks: Optional[int] = None,
    group_size: Optional[int] = None,
) -> jax.Array:
    """The paper's 2-D chain: sqrt(n) chains of sqrt(n), then a chain over
    the group roots, then broadcast back down both levels.

    Implemented as four masked pipelined chain passes: within-group chains
    all run concurrently (disjoint ppermute edges), then the root chain
    runs, then the two broadcast passes mirror back.
    """
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    g, m = two_level_group_sizes(n, group_size)  # groups of size g, m groups
    idx = lax.axis_index(axis_name)
    C = num_chunks or autotune_num_chunks(g + m, x.size * x.dtype.itemsize)
    buf, orig = _to_chunks(x, C)
    pos = idx % g  # place within its group
    group = idx // g
    is_root = pos == g - 1
    perm_in = [(q * g + a, q * g + b) for q in range(m) for a, b in _up(g)]
    perm_root = [(a * g + g - 1, b * g + g - 1) for a, b in _up(m)]
    # 1. reduce along each group into its last rank, the group's root
    buf = _chain_pass(buf, axis_name, perm_in, pos, pos >= 1, C + g - 2, add=True)
    # 2. reduce along the roots into the last group's
    buf = _chain_pass(buf, axis_name, perm_root, group, is_root & (group >= 1),
                      C + m - 2, add=True)
    # 3. broadcast back along the roots
    buf = _chain_pass(buf, axis_name, _flip(perm_root), (m - 1) - group,
                      is_root & (group <= m - 2), C + m - 2, add=False)
    # 4. broadcast down each group
    buf = _chain_pass(buf, axis_name, _flip(perm_in), (g - 1) - pos, pos <= g - 2,
                      C + g - 2, add=False)
    return _from_chunks(buf, orig, x.shape, x.dtype)


# ---------------------------------------------------------------------------
# beyond-paper: bandwidth-optimal ring forms
# ---------------------------------------------------------------------------


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring reduce-scatter: returns this rank's 1/n sum shard (flattened).

    The paper notes its API cannot express ring-allreduce (section 7); we
    implement it anyway as the beyond-paper optimized gradient path."""
    n = lax.psum(1, axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shards = flat.reshape(n, -1)
    if n == 1:
        return shards[0]
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        send_k = (idx - t) % n
        payload = _dyn_chunk(carry, send_k)
        recv = lax.ppermute(payload, axis_name, perm)
        recv_k = (idx - t - 1) % n
        return _add_chunk(carry, recv_k, recv)

    shards = lax.fori_loop(0, n - 1, body, shards)
    return _dyn_chunk(shards, (idx + 1) % n)


def ring_all_gather(shard: jax.Array, axis_name: str) -> jax.Array:
    """Ring all-gather of equal shards -> (n, shard_elems)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return shard[None]
    idx = lax.axis_index(axis_name)
    out = jnp.zeros((n,) + shard.shape, shard.dtype)
    out = _set_chunk(out, (idx + 1) % n, shard)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        send_k = (idx + 1 - t) % n
        payload = _dyn_chunk(carry, send_k)
        recv = lax.ppermute(payload, axis_name, perm)
        recv_k = (idx - t) % n
        return _set_chunk(carry, recv_k, recv)

    return lax.fori_loop(0, n - 1, body, out)


def rs_ag_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """reduce-scatter + all-gather allreduce (bandwidth-optimal)."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shard = ring_reduce_scatter(x, axis_name)
    gathered = ring_all_gather(shard, axis_name)
    # ring_all_gather seeds rank i's shard at its logical slot (i+1)%n and
    # rotates consistently, so `gathered` is already in logical chunk order.
    flat = gathered.reshape(-1)
    orig = x.size
    return flat[:orig].reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# dispatcher: the nBL>S rule with TPU constants
# ---------------------------------------------------------------------------


def _allreduce(x: jax.Array, axis_name: str, method: str, config: CollectiveConfig, n: int):
    """``x`` summed over the ``n`` ranks of ``axis_name`` by ``method``, one
    of ``grad_sync``'s methods (which ``config.choose`` also returns)."""
    nbytes = x.size * x.dtype.itemsize
    if method == "psum":
        return lax.psum(x, axis_name)
    if method == "hoplite_chain":
        return chain_allreduce(x, axis_name, config.chunks_for(n, nbytes))
    if method == "hoplite_2d":
        return two_level_allreduce(x, axis_name, config.chunks_for_2d(n, nbytes))
    raise ValueError(f"unknown grad_sync method {method!r}")


def hoplite_psum(
    x: jax.Array,
    axis_name: str,
    config: CollectiveConfig = ICI_CONFIG,
    axis_size: Optional[int] = None,
) -> jax.Array:
    """Hoplite-scheduled allreduce over one named axis.

    Dispatch (static, at trace time, ``config.choose``):
      * small tensor          -> lax.psum   (directory-inline analogue)
      * n*B*L <= S            -> fused 1-D chain allreduce
      * n*B*L  > S            -> 2-D sqrt(n) chain allreduce
    """
    n = axis_size if axis_size is not None else lax.psum(1, axis_name)
    method = config.choose(n, x.size * x.dtype.itemsize)
    return _allreduce(x, axis_name, method, config, n)


@jax.named_scope("grad_sync")
def grad_sync(
    grads,
    axis_name: str,
    method: str,
    config: CollectiveConfig = ICI_CONFIG,
    mean: bool = True,
):
    """Synchronize a gradient pytree over ``axis_name``, leaf by leaf.

    methods: 'psum' (XLA's allreduce), 'hoplite_chain' (the fused chain),
    'hoplite_2d' (the 2-D sqrt(n) chain), each chain in the chunks
    ``config`` plans for the leaf.
    """
    n = lax.psum(1, axis_name)

    def one(g):
        out = _allreduce(g, axis_name, method, config, n)
        return out / n if mean else out

    return jax.tree_util.tree_map(one, grads)
