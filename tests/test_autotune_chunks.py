"""Chunk-count autotuning (Appendix-A cost model, core/planner.py)."""

import numpy as np
import pytest

from repro.core.planner import (
    DCN_LINK,
    ICI_CONFIG,
    ICI_LINK,
    MAX_NUM_CHUNKS,
    MIN_CHUNK_BYTES,
    POD_CONFIG,
    CollectiveConfig,
    autotune_num_chunks,
)


def test_chosen_c_is_monotone_in_size():
    """The optimal chunk count C* = sqrt((2n-3)S/(BL)) must be monotone
    nondecreasing in the object size S, for every axis size and link."""
    sizes = [1 << k for k in range(10, 34)]  # 1 KB .. 8 GB
    for n in (2, 4, 8, 16, 64, 256):
        for link in (ICI_LINK, DCN_LINK):
            cs = [autotune_num_chunks(n, s, link) for s in sizes]
            assert cs == sorted(cs), (n, link, cs)
            assert all(1 <= c <= MAX_NUM_CHUNKS for c in cs)


def test_monotone_in_chain_length():
    """Longer chains amortize more latency per chunk: C nondecreasing in n."""
    ns = [2, 3, 4, 8, 16, 32, 128]
    cs = [autotune_num_chunks(n, 64 << 20, ICI_LINK) for n in ns]
    assert cs == sorted(cs)


def test_matches_cost_model_argmin():
    """The closed form must agree with brute-force argmin of
    T(C) = (C + 2n - 3)(L_eff + (S/C)/B) within the clamp range."""
    n, S = 8, 16 << 20
    link, overhead = ICI_LINK, 2e-6
    L = link.latency + overhead

    def t(c):
        return (c + 2 * n - 3) * (L + (S / c) / link.bandwidth)

    brute = min(range(1, MAX_NUM_CHUNKS + 1), key=t)
    chosen = autotune_num_chunks(n, S, link, overhead)
    # Within 2x of brute force (integer truncation of the continuous optimum);
    # and the achieved time within 5% of optimal.
    assert brute / 2 <= chosen <= brute * 2
    assert t(chosen) <= 1.05 * t(brute)


def test_chunks_never_below_min_bytes():
    c = autotune_num_chunks(256, 4096, ICI_LINK)
    assert 4096 // c >= MIN_CHUNK_BYTES


def test_explicit_override_kept():
    cfg = CollectiveConfig(num_chunks=7)
    assert cfg.chunks_for(16, 1 << 30) == 7
    # Default configs autotune: size-sensitive, not a hardcoded constant.
    big = ICI_CONFIG.chunks_for(16, 1 << 30)
    small = ICI_CONFIG.chunks_for(16, 1 << 20)
    assert big > small >= 1


def test_dcn_uses_fewer_chunks_than_ici_for_same_shape():
    """Higher per-step latency (the DCN constants of the trainer's pod axis)
    pushes toward fewer, larger chunks."""
    S = 64 << 20
    assert POD_CONFIG.chunks_for(16, S) <= ICI_CONFIG.chunks_for(16, S)
