"""TPU-native Hoplite collectives: chunk-pipelined chain/tree schedules.

This is the hardware adaptation of the paper's data plane (DESIGN.md §2).
On TPU the only inter-chip data path is an XLA collective, so Hoplite's
transfer schedules are expressed as explicit ``jax.lax.ppermute`` programs
inside ``shard_map``:

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
    groups, chain across group roots, broadcast back.  Selected by the
    paper's condition n*B*L > S evaluated with ICI/DCN constants.
  * ``binomial_broadcast`` -- the MPI-style static tree, kept as a baseline
    (and used where a true one-to-all of a *replicated-source* is needed).
  * ``ring_reduce_scatter`` / ``ring_all_gather`` -- beyond-paper,
    bandwidth-optimal forms used by the optimized gradient sync path.
  * ``hoplite_psum`` -- the dispatcher: tiny tensors go straight to
    ``lax.psum`` (the TPU analogue of the <64 KB directory-inline fast
    path); large tensors pick 1-D vs 2-D chains via nBL > S.

All functions assume they run inside ``shard_map`` with ``axis_name``
available, and operate on the *local* shard.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import planner
from repro.core.planner import LinkSpec, ICI_LINK, DCN_LINK

# TPU analogue of the paper's 64 KB small-object threshold: below this a
# plain psum beats any software-pipelined schedule (latency-bound regime).
SMALL_TENSOR_BYTES = 256 * 1024

# Autotuned chunk-count clamp: at least one chunk, at most this many
# ppermute steps per leg, and never chunks smaller than MIN_CHUNK_BYTES
# (tiny ppermute payloads are pure launch overhead).
MAX_NUM_CHUNKS = 256
MIN_CHUNK_BYTES = 1024


def autotune_num_chunks(
    axis_size: int,
    nbytes: int,
    link: LinkSpec = ICI_LINK,
    step_overhead: float = 2e-6,
) -> int:
    """Appendix-A optimal chunk count for a fused chain schedule.

    The fused chain allreduce runs ``C + 2n - 3`` ppermute steps of
    ``S/C`` bytes, so with per-step latency ``L`` (link latency plus
    software launch/sync overhead):

        T(C) = (C + 2n - 3) * (L + S/(C*B))
             = C*L + S/B + (2n-3)*L + (2n-3)*S/(C*B)

    dT/dC = L - (2n-3)*S/(B*C^2) = 0  gives

        C* = sqrt((2n-3) * S / (B * L))

    -- more chunks for bigger objects (monotone nondecreasing in S,
    unit-tested) and longer chains, fewer when per-step latency dominates.
    Clamped to [1, MAX_NUM_CHUNKS] and to chunks of >= MIN_CHUNK_BYTES.
    """
    n = max(2, axis_size)
    eff_latency = link.latency + step_overhead
    c_opt = math.sqrt((2 * n - 3) * nbytes / (link.bandwidth * eff_latency))
    c = int(max(1.0, c_opt))
    c = min(c, MAX_NUM_CHUNKS, max(1, nbytes // MIN_CHUNK_BYTES))
    return c


def two_level_group_sizes(n: int, group_size: Optional[int] = None):
    """(g, m): groups of size ``g``, ``m`` groups, for the 2-D sqrt(n)
    chain -- g grows until it divides n (static perms need even groups).
    The effective chain length of the 2-D schedule is ~``g + m``, which is
    what chunk autotuning must use (not the 1-D length n)."""
    g = group_size or max(2, math.isqrt(n))
    while n % g != 0:
        g += 1
    return g, n // g


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


# ---------------------------------------------------------------------------
# fused chain allreduce (the paper's reduce->broadcast, streamed)
# ---------------------------------------------------------------------------


def pairwise_exchange_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """n == 2 degenerate chain: one bidirectional exchange.

    For two pods the 1-D chain IS a pairwise exchange (send-all /
    receive-all on full-duplex links), and crucially it needs NO flat
    reshape -- under partial-manual shard_map a reshape of a tensor that
    is still sharded over the auto (data/model) axes forces GSPMD to
    replicate it (observed: 600 GiB/device temp on the qwen2-vl-72b
    multi-pod train cell, EXPERIMENTS §Perf iteration 5)."""
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
    perm_up = [(i, i + 1) for i in range(n - 1)]
    perm_down = [(i + 1, i) for i in range(n - 1)]
    total_steps = C + 2 * n - 3

    def body(t, carry):
        acc, fin = carry
        # ---- reduce leg: i sends acc[t-i] to i+1, which accumulates ----
        k_send = t - idx
        r_payload = _dyn_chunk(acc, k_send)
        r_recv = lax.ppermute(r_payload, axis_name, perm_up)
        k_recv = t - idx + 1
        r_ok = (idx >= 1) & (k_recv >= 0) & (k_recv < C)
        acc = _add_chunk(acc, k_recv, jnp.where(r_ok, r_recv, 0).astype(acc.dtype))
        # ---- broadcast leg: i sends final[t - 2(n-1) + i] to i-1 ----
        k_bsend = t - 2 * (n - 1) + idx
        src = jnp.where(idx == n - 1, _dyn_chunk(acc, k_bsend), _dyn_chunk(fin, k_bsend))
        b_recv = lax.ppermute(src, axis_name, perm_down)
        k_brecv = t - 2 * (n - 1) + idx + 1
        b_ok = (idx <= n - 2) & (k_brecv >= 0) & (k_brecv < C)
        cur = _dyn_chunk(fin, k_brecv)
        fin = _set_chunk(fin, k_brecv, jnp.where(b_ok, b_recv, cur))
        return acc, fin

    acc, fin = lax.fori_loop(0, total_steps, body, (acc, fin))
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
    perm_up = [(i, i + 1) for i in range(n - 1)]

    def body(t, acc):
        k_send = t - idx
        recv = lax.ppermute(_dyn_chunk(acc, k_send), axis_name, perm_up)
        k_recv = t - idx + 1
        ok = (idx >= 1) & (k_recv >= 0) & (k_recv < C)
        return _add_chunk(acc, k_recv, jnp.where(ok, recv, 0).astype(acc.dtype))

    acc = lax.fori_loop(0, C + n - 2, body, acc)
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
        perm = [(i + 1, i) for i in range(n - 1)]
        pos = (n - 1) - idx  # hops from root
    else:
        perm = [(i, i + 1) for i in range(n - 1)]
        pos = idx

    def body(t, buf):
        k_send = t - pos
        recv = lax.ppermute(_dyn_chunk(buf, k_send), axis_name, perm)
        k_recv = t - pos + 1
        ok = (pos >= 1) & (k_recv >= 0) & (k_recv < C)
        cur = _dyn_chunk(buf, k_recv)
        return _set_chunk(buf, k_recv, jnp.where(ok, recv, cur))

    buf = lax.fori_loop(0, C + n - 2, body, buf)
    return _from_chunks(buf, orig, x.shape, x.dtype)


def binomial_broadcast(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """MPI-style static binomial tree broadcast (log2 n rounds, store &
    forward).  Baseline for EXPERIMENTS §Perf comparisons."""
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

    Implemented as masked pipelined chain passes: within-group chains all
    run concurrently (disjoint ppermute edges), then the root chain runs,
    then the two broadcast legs mirror back.
    """
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    g, m = two_level_group_sizes(n, group_size)  # groups of size g, m groups
    idx = lax.axis_index(axis_name)
    C = num_chunks or autotune_num_chunks(g + m, x.size * x.dtype.itemsize)
    buf, orig = _to_chunks(x, C)
    in_group_pos = idx % g
    group_id = idx // g

    # ---- phase 1: pipelined chain reduce within each group -> local root
    perm_in = [
        (q * g + j, q * g + j + 1) for q in range(m) for j in range(g - 1)
    ]

    def red_body_in(t, b):
        k_send = t - in_group_pos
        recv = lax.ppermute(_dyn_chunk(b, k_send), axis_name, perm_in)
        k_recv = t - in_group_pos + 1
        ok = (in_group_pos >= 1) & (k_recv >= 0) & (k_recv < C)
        return _add_chunk(b, k_recv, jnp.where(ok, recv, 0).astype(b.dtype))

    buf = lax.fori_loop(0, C + g - 2, red_body_in, buf)

    # ---- phase 2: chain reduce across group roots (ranks q*g + g-1)
    perm_root = [(q * g + g - 1, (q + 1) * g + g - 1) for q in range(m - 1)]
    is_root = in_group_pos == g - 1

    def red_body_root(t, b):
        k_send = t - group_id
        recv = lax.ppermute(_dyn_chunk(b, k_send), axis_name, perm_root)
        k_recv = t - group_id + 1
        ok = is_root & (group_id >= 1) & (k_recv >= 0) & (k_recv < C)
        return _add_chunk(b, k_recv, jnp.where(ok, recv, 0).astype(b.dtype))

    buf = lax.fori_loop(0, C + m - 2, red_body_root, buf)

    # ---- phase 3: broadcast back across roots (reverse chain)
    perm_root_down = [((q + 1) * g + g - 1, q * g + g - 1) for q in range(m - 1)]
    root_pos_down = (m - 1) - group_id

    def bc_body_root(t, b):
        k_send = t - root_pos_down
        recv = lax.ppermute(_dyn_chunk(b, k_send), axis_name, perm_root_down)
        k_recv = t - root_pos_down + 1
        ok = is_root & (group_id <= m - 2) & (k_recv >= 0) & (k_recv < C)
        cur = _dyn_chunk(b, k_recv)
        return _set_chunk(b, k_recv, jnp.where(ok, recv, cur))

    buf = lax.fori_loop(0, C + m - 2, bc_body_root, buf)

    # ---- phase 4: broadcast down within each group (reverse chain)
    perm_in_down = [
        (q * g + j + 1, q * g + j) for q in range(m) for j in range(g - 1)
    ]
    pos_down = (g - 1) - in_group_pos

    def bc_body_in(t, b):
        k_send = t - pos_down
        recv = lax.ppermute(_dyn_chunk(b, k_send), axis_name, perm_in_down)
        k_recv = t - pos_down + 1
        ok = (in_group_pos <= g - 2) & (k_recv >= 0) & (k_recv < C)
        cur = _dyn_chunk(b, k_recv)
        return _set_chunk(b, k_recv, jnp.where(ok, recv, cur))

    buf = lax.fori_loop(0, C + g - 2, bc_body_in, buf)
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


@dataclasses.dataclass(frozen=True)
class CollectiveConfig:
    """Selection policy for one mesh axis (paper section 4.3 + App. A).

    ``num_chunks=None`` (the default) derives the chunk count per
    collective from the Appendix-A cost model -- ``autotune_num_chunks``
    over (axis_size, nbytes, link, step_overhead).  An explicit integer
    pins it (benchmark sweeps, regression repro)."""

    link: LinkSpec = ICI_LINK
    num_chunks: Optional[int] = None
    small_bytes: int = SMALL_TENSOR_BYTES
    # per-ppermute-step software overhead (launch + sync), seconds; this is
    # the 'L' that actually matters for chunked schedules on TPU.
    step_overhead: float = 2e-6

    def effective_latency(self) -> float:
        return self.link.latency + self.step_overhead

    def chunks_for(self, axis_size: int, nbytes: int) -> int:
        """Chunk count for a 1-D chain over ``axis_size`` ranks."""
        if self.num_chunks is not None:
            return self.num_chunks
        return autotune_num_chunks(axis_size, nbytes, self.link, self.step_overhead)

    def chunks_for_2d(self, axis_size: int, nbytes: int) -> int:
        """Chunk count for the 2-D schedule, whose chain length is the
        two-level g + m, not the 1-D axis_size."""
        if self.num_chunks is not None:
            return self.num_chunks
        g, m = two_level_group_sizes(axis_size)
        return autotune_num_chunks(g + m, nbytes, self.link, self.step_overhead)

    def choose(self, axis_size: int, nbytes: int) -> str:
        if nbytes < self.small_bytes or axis_size <= 2:
            return "psum"
        eff = LinkSpec(self.link.bandwidth, self.effective_latency())
        if planner.use_two_dimensional(axis_size, eff, nbytes):
            return "chain2d"
        return "chain"


ICI_CONFIG = CollectiveConfig(link=ICI_LINK)
DCN_CONFIG = CollectiveConfig(link=DCN_LINK, step_overhead=10e-6)


def hoplite_psum(
    x: jax.Array,
    axis_name: str,
    config: CollectiveConfig = ICI_CONFIG,
    axis_size: Optional[int] = None,
) -> jax.Array:
    """Hoplite-scheduled allreduce over one named axis.

    Dispatch (static, at trace time):
      * small tensor          -> lax.psum   (directory-inline analogue)
      * n*B*L <= S            -> fused 1-D chain allreduce
      * n*B*L  > S            -> 2-D sqrt(n) chain allreduce
    """
    n = axis_size if axis_size is not None else lax.psum(1, axis_name)
    nbytes = x.size * x.dtype.itemsize
    method = config.choose(n, nbytes)
    if method == "psum":
        return lax.psum(x, axis_name)
    if method == "chain2d":
        return two_level_allreduce(x, axis_name, config.chunks_for_2d(n, nbytes))
    return chain_allreduce(x, axis_name, config.chunks_for(n, nbytes))


@jax.named_scope("grad_sync")
def grad_sync(
    grads,
    axis_name: str,
    method: str = "hoplite",
    config: CollectiveConfig = ICI_CONFIG,
    mean: bool = True,
):
    """Synchronize a gradient pytree over ``axis_name``.

    methods: 'psum' (XLA baseline), 'hoplite' (paper-faithful dispatch),
    'chain' / 'chain2d' (forced), 'rs_ag' (beyond-paper ring).
    """
    n = lax.psum(1, axis_name)

    def one(g):
        if method == "psum":
            out = lax.psum(g, axis_name)
        elif method == "hoplite":
            out = hoplite_psum(g, axis_name, config)
        elif method == "chain":
            out = chain_allreduce(
                g, axis_name, config.chunks_for(n, g.size * g.dtype.itemsize)
            )
        elif method == "chain2d":
            out = two_level_allreduce(
                g, axis_name, config.chunks_for_2d(n, g.size * g.dtype.itemsize)
            )
        elif method == "rs_ag":
            out = rs_ag_allreduce(g, axis_name)
        else:
            raise ValueError(f"unknown grad_sync method {method!r}")
        return out / n if mean else out

    return jax.tree_util.tree_map(one, grads)


def partial_fold_scale(mask) -> float:
    """Unbiased-mean correction for a bounded-time partial SUM fold.

    ``LocalCluster.allreduce(..., deadline=, min_participants=)`` returns
    the exact SUM of the *participating* contributions (``mask[i]`` True)
    -- it never rescales the bytes it folds.  A data-parallel trainer
    that divides the synchronized gradient by the WORLD size would bias
    it low by ``kept/n``; multiply the partial sum by this factor
    (``n / kept``) first so ``scaled_sum / n`` equals the mean over the
    participants -- an unbiased estimate of the full mean when straggler
    identity is independent of the gradient (the usual assumption; see
    README "Fault injection and bounded-time collectives" for when it is
    not).  Pure Python on the participation mask -- no jax required.
    """
    mask = tuple(bool(m) for m in mask)
    kept = sum(mask)
    if kept == 0:
        raise ValueError("partial_fold_scale: empty participation mask")
    return len(mask) / kept
