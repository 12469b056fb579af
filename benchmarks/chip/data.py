"""The benchmark's own copy of the trainer's seeded batch generator.

Copied from ``repro/data/pipeline.py`` (``_tokens_for``, ``host_batch``).
The timed window is fed by the program's ``Prefetcher``; the reference is
fed from this copy, so a change to the data layer that alters what the
program trains on shows as a gap between the two.
"""

from __future__ import annotations

import numpy as np


def tokens_for(seed: int, step: int, row: int, seq: int, vocab: int) -> np.ndarray:
    """Row ``row`` of step ``step``: ``seq`` token ids from a counter-based RNG."""
    key = (seed * 0x9E3779B1 + step * 0x85EBCA77 + row * 0xC2B2AE3D) & 0xFFFFFFFF
    rng = np.random.Generator(np.random.PCG64(key))
    return rng.integers(0, vocab, size=(seq,), dtype=np.int32)


def host_batch(program: dict, traffic: dict, step: int, seed: int) -> dict:
    """The global batch of ``step``: next-token pairs over ``seq_len`` and,
    for an encoder-decoder, the stub frontend's frame embeddings."""
    B, S = traffic["global_batch"], traffic["seq_len"]
    toks = np.stack([tokens_for(seed, step, r, S + 1, program["vocab_size"]) for r in range(B)])
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if program.get("encoder_layers", 0):
        rng = np.random.Generator(np.random.PCG64(seed * 7919 + step))
        batch["encoder_frames"] = rng.standard_normal(
            (B, program["encoder_seq"], program["d_model"]), dtype=np.float32
        )
    return batch
