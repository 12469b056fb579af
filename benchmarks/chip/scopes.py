"""Device time per program layer, read from the traced segment's op names.

The train step puts a ``jax.named_scope`` on each layer boundary
(``SCOPES``).  The compiler writes the scope into every op's ``op_name``
metadata, which the chip's trace carries as the op's ``tf_op`` stat:
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/dot_general``.
Each op event on ``XLA Ops`` and ``Async XLA Ops`` (bar the containers of
``trace_reduce.CONTAINERS``) belongs to the scope that is a whole ``/``
segment of its ``tf_op``.  The names are copied here, not imported from
the program: a scope renamed there reads as missing here.

Per chip, over the last ``bench/window`` span, per traced step:

* ``<scope>_ms``: the union of the scope's op intervals (``grad_sync`` gives
  ``sync_ms``);
* ``sync_exposed_ms``: the part of that sync union in which no other op
  of ``XLA Ops`` runs;
* ``sync_launches``: the collective ops (``trace_reduce.COLLECTIVE``, a
  ``-done`` half not counted) under ``grad_sync`` on ``XLA Ops``;

each the mean over the cell's chips.  From the host plane,
``data_produce_ms``: the mean length of the ``data/produce`` spans (the
data pipeline's producer, one batch each) that end inside the window.

A reading is None where its scope or span is absent, as in a program
without them.  The run's trace is the newest under ``harness.TRACE_DIR``
(the readers' ``ctx`` carries no path), and it counts only if its window
is the one the harness reduced (``ctx["trace"]["window_s"]``, to the
nanosecond).  The file is parsed once per run and shared by all readers.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip import trace_reduce as tr

SCOPES = ("attention", "mlp", "optimizer", "grad_sync")
SYNC = SCOPES.index("grad_sync")
METRIC = {"attention": "attention_ms", "mlp": "mlp_ms", "optimizer": "optimizer_ms",
          "grad_sync": "sync_ms"}
PRODUCE_SPAN = "data/produce"
TF_OP = "tf_op"


@dataclasses.dataclass
class ChipOps:
    """One chip's op events: event i ran over [start[i], end[i]) ns."""

    scope: np.ndarray  # index into SCOPES, -1 for none
    launch: np.ndarray  # a collective launch (trace_reduce.COLLECTIVE, not a -done half)
    container: np.ndarray  # a trace_reduce.CONTAINERS op, whose time is its body ops'
    start: np.ndarray
    end: np.ndarray
    is_async: np.ndarray

    @classmethod
    def from_events(cls, events: Sequence[Tuple[str, str, float, float]], asynchronous=()):
        """From (HLO name, tf_op, start ns, end ns) events of each line."""
        evs = list(events) + list(asynchronous)
        kinds = zip(*(_kind(name, tf_op) for name, tf_op, _, _ in evs))
        return cls(*map(np.array, kinds),
                   np.array([s for _, _, s, _ in evs], dtype=np.float64),
                   np.array([e for _, _, _, e in evs], dtype=np.float64),
                   np.array([False] * len(events) + [True] * len(asynchronous)))


def scope_of(tf_op: str) -> int:
    """The index of the first scope that is a whole segment of ``tf_op``; -1."""
    segments = set(tf_op.split("/"))
    return next((i for i, s in enumerate(SCOPES) if s in segments), -1)


def _kind(hlo: str, tf_op: str) -> Tuple[int, bool, bool]:
    stem = tr.short_name(hlo).split(".")[0]
    return (scope_of(tf_op), bool(tr.COLLECTIVE.match(stem)) and not stem.endswith("-done"),
            stem in tr.CONTAINERS)


def _schema():
    """``trace_reduce``'s minimal XPlane schema, with the op metadata's stats."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_chip_scopes.proto",
                                             package="bench_chip_scopes")

    def msg(name, fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".bench_chip_scopes." + tname

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("XStat", [("metadata_id", 1, F.TYPE_INT64, one, None), ("str_value", 5, F.TYPE_STRING, one, None),
                  ("ref_value", 7, F.TYPE_UINT64, one, None)])
    msg("XEventMetadata", [("id", 1, F.TYPE_INT64, one, None), ("name", 2, F.TYPE_STRING, one, None),
                           ("stats", 5, F.TYPE_MESSAGE, rep, "XStat")])
    msg("XStatMetadata", [("id", 1, F.TYPE_INT64, one, None), ("name", 2, F.TYPE_STRING, one, None)])
    msg("MetadataEntry", [("key", 1, F.TYPE_INT64, one, None),
                          ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata")])
    msg("StatMetadataEntry", [("key", 1, F.TYPE_INT64, one, None),
                              ("value", 2, F.TYPE_MESSAGE, one, "XStatMetadata")])
    msg("XEvent", [("metadata_id", 1, F.TYPE_INT64, one, None), ("offset_ps", 2, F.TYPE_INT64, one, None),
                   ("duration_ps", 3, F.TYPE_INT64, one, None)])
    msg("XLine", [("name", 2, F.TYPE_STRING, one, None), ("timestamp_ns", 3, F.TYPE_INT64, one, None),
                  ("events", 4, F.TYPE_MESSAGE, rep, "XEvent")])
    msg("XPlane", [("name", 2, F.TYPE_STRING, one, None), ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
                   ("event_metadata", 4, F.TYPE_MESSAGE, rep, "MetadataEntry"),
                   ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry")])
    msg("XSpace", [("planes", 1, F.TYPE_MESSAGE, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_chip_scopes.XSpace"))


def _tf_op(meta, stat_names: Dict[int, str]) -> str:
    for st in meta.stats:
        if stat_names.get(st.metadata_id) == TF_OP:
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def load(path: str) -> Tuple[Dict[int, ChipOps], List[tr.Span]]:
    """({chip id: its ops}, the host's ``bench/window`` and ``data/produce``
    spans) of one trace file."""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    chips: Dict[int, ChipOps] = {}
    spans: List[tr.Span] = []
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            index = {e.key: i for i, e in enumerate(plane.event_metadata)}
            kinds = [_kind(e.value.name, _tf_op(e.value, stat_names)) for e in plane.event_metadata]
            # one row per metadata entry, and a last one for an event whose entry is missing
            table = [np.array(col) for col in zip(*kinds, (-1, False, False))]
            lookup = np.vectorize(lambda k: index.get(k, -1), otypes=[np.int64])
            parts = []
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, tr.ASYNC_LINE) and len(line.events):
                    ids, s, e = tr._line_arrays(line)
                    rows = lookup(ids)
                    parts.append((*(col[rows] for col in table), s, e,
                                  np.full(len(ids), line.name == tr.ASYNC_LINE)))
            if parts:
                chips[int(m.group(1))] = ChipOps(*[np.concatenate(x) for x in zip(*parts)])
        elif plane.name == tr.HOST_PLANE:
            meta = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for e in line.events:
                    name = meta.get(e.metadata_id, "")
                    if name in (tr.WINDOW_SPAN, PRODUCE_SPAN):
                        s = line.timestamp_ns + e.offset_ps * 1e-3
                        spans.append((name, s, s + e.duration_ps * 1e-3))
    return chips, spans


def _length(start: np.ndarray, end: np.ndarray) -> float:
    s, e = tr.union(start, end)
    return float(np.sum(e - s))


def reduce(chips: Dict[int, ChipOps], spans: Sequence[tr.Span], n_chips: int,
           steps: int) -> Optional[dict]:
    """The readings over the last ``bench/window`` span for the first
    ``n_chips`` chips, per step of the ``steps`` traced; None without a
    window or a chip."""
    windows = [(s, e) for name, s, e in spans if name == tr.WINDOW_SPAN]
    ids = sorted(chips)[:n_chips]
    if not windows or not ids:
        return None
    lo, hi = windows[-1]
    per_scope = np.zeros(len(SCOPES))
    seen = np.zeros(len(SCOPES), bool)
    exposed = launches = 0.0
    for i in ids:
        ops = chips[i]
        inside = (ops.end > lo) & (ops.start < hi) & ~ops.container
        s, e = np.clip(ops.start[inside], lo, hi), np.clip(ops.end[inside], lo, hi)
        scope, asyn = ops.scope[inside], ops.is_async[inside]
        for k in range(len(SCOPES)):
            mine = scope == k
            seen[k] |= bool(mine.any())
            per_scope[k] += _length(s[mine], e[mine])
        sync = scope == SYNC
        other = ~sync & ~asyn
        # the sync time that no other op covers: |sync U other| - |other|
        exposed += _length(s[sync | other], e[sync | other]) - _length(s[other], e[other])
        launches += int(np.sum(sync & ops.launch[inside] & ~asyn))
    per_step_ms = 1e-6 / (len(ids) * steps)
    out = {"window_s": (hi - lo) * 1e-9}
    for k, name in enumerate(SCOPES):
        out[METRIC[name]] = float(per_scope[k] * per_step_ms) if seen[k] else None
    out["sync_exposed_ms"] = exposed * per_step_ms if seen[SYNC] else None
    out["sync_launches"] = launches / (len(ids) * steps) if seen[SYNC] else None
    produced = [e - s for name, s, e in spans if name == PRODUCE_SPAN and lo < e <= hi]
    out["data_produce_ms"] = float(np.mean(produced)) * 1e-6 if produced else None
    return out


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float, n_chips: int, steps: int) -> Optional[dict]:
    t0 = time.perf_counter()
    out = reduce(*load(path), n_chips=n_chips, steps=steps)
    print(f"timing scopes {time.perf_counter() - t0:.1f} s (trace parse and per-scope reduction)",
          file=sys.stderr)
    return out


def reading(ctx: dict, name: str) -> Optional[float]:
    """The traced run's reading ``name`` (a metric's name), or None."""
    if not ctx["trace"]:
        return None
    try:
        path = tr.find_xspace(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    r = _reduced(path, os.path.getmtime(path), ctx["chips"], ctx["trace_steps"])
    if r is None or abs(r["window_s"] - ctx["trace"]["window_s"]) > 1e-9:
        return None
    return r[name]
