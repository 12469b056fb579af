"""Deterministic synthetic token pipeline with device-sharded delivery.

Every (step, batch_row) is a pure function of the seed, so any host in a
multi-host deployment can materialize exactly its addressable shard via
``jax.make_array_from_callback`` -- no host-to-host data traffic, no
skew between restarts (critical for checkpoint/restart determinism: the
pipeline is resumed by step index, not by iterator state).

A background prefetch thread keeps ``prefetch`` batches ready so host
data generation overlaps device compute.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec


def _tokens_for(
    seed: int, step: int, row: int, seq: int, vocab: int, structured: bool = False
) -> np.ndarray:
    """Deterministic per-row token generator (counter-based RNG).

    structured=True emits arithmetic sequences t[i+1] = (t[i] + d) % vocab
    with a per-row stride d in 1..8 -- the stride is inferable in-context
    from the first two tokens, so a trained LM's loss collapses toward 0
    (used by examples/train_lm.py to demonstrate real learning)."""
    key = (seed * 0x9E3779B1 + step * 0x85EBCA77 + row * 0xC2B2AE3D) & 0xFFFFFFFF
    rng = np.random.Generator(np.random.PCG64(key))
    if structured:
        start = int(rng.integers(0, vocab))
        stride = int(rng.integers(1, 9))
        return ((start + stride * np.arange(seq, dtype=np.int64)) % vocab).astype(
            np.int32
        )
    return rng.integers(0, vocab, size=(seq,), dtype=np.int32)


def host_batch(
    cfg: ModelConfig, shape: ShapeSpec, step: int, seed: int = 0, structured: bool = False
) -> Dict[str, np.ndarray]:
    B, S = shape.global_batch, shape.seq_len
    toks = np.stack(
        [_tokens_for(seed, step, r, S + 1, cfg.vocab_size, structured) for r in range(B)]
    )
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if cfg.rope == "mrope":
        batch["positions_3d"] = np.broadcast_to(
            np.arange(S, dtype=np.int32)[None, None], (3, B, S)
        ).copy()
    if cfg.is_encoder_decoder:
        rng = np.random.Generator(np.random.PCG64(seed * 7919 + step))
        batch["encoder_frames"] = rng.standard_normal(
            (B, cfg.encoder_seq, cfg.d_model), dtype=np.float32
        )
    return batch


def device_batch(
    cfg, shape, step, mesh: Mesh, specs: Dict[str, P], seed: int = 0, structured: bool = False
):
    """Materialize a global batch directly into sharded jax.Arrays."""
    host = host_batch(cfg, shape, step, seed, structured)
    out = {}
    for name, arr in host.items():
        sharding = NamedSharding(mesh, specs[name])
        out[name] = jax.make_array_from_callback(
            arr.shape, sharding, lambda idx, a=arr: a[idx]
        )
    return out


class Prefetcher:
    """Background-thread batch prefetch (overlap host gen with device step).

    A producer failure (e.g. a batch that cannot be placed on the mesh)
    ends the thread and is re-raised by every later ``next()``, so the
    consumer fails instead of waiting forever."""

    def __init__(self, cfg, shape, mesh, specs, start_step: int = 0, seed: int = 0, depth: int = 2):
        self.cfg, self.shape, self.mesh, self.specs, self.seed = cfg, shape, mesh, specs, seed
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.step = start_step
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        step = self.step
        try:
            while not self.stop.is_set():
                with jax.profiler.TraceAnnotation("data/produce"):
                    batch = device_batch(self.cfg, self.shape, step, self.mesh, self.specs, self.seed)
                self.q.put((step, batch))  # back-pressure, outside the span
                step += 1
        except Exception as e:  # noqa: BLE001 -- re-raised by the consumer
            self.q.put(e)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        item = self.q.get()
        if isinstance(item, Exception):
            self.q.put(item)  # the producer is gone: later calls raise too
            raise item
        return item

    def close(self):
        self.stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
