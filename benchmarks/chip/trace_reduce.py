"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

The benchmark's own host spans (``bench/...``, written by
``jax.profiler.TraceAnnotation``) and the chips' op events share the
trace's clock.  The last ``bench/window`` span marks the traced window, and
everything is clipped to it.  Per chip:

* busy: the union of the intervals in which an op of the ``XLA Ops`` line ran
  (ops nest inside ``while`` loops; the union counts each instant once);
* collective: the union of the intervals of the ops, on ``XLA Ops`` and
  ``Async XLA Ops``, whose HLO name marks a collective (``COLLECTIVE``);
* gaps: the complement of busy, each labelled with the benchmark span that
  covers most of it, i.e. what the host was doing while the chip waited.

A whole-model step holds hundreds of thousands of ops, so the file is read
with a minimal copy of the XPlane protobuf schema (field numbers of
``tsl/profiler/protobuf/xplane.proto``) into numpy arrays, not op by op.
No TPU library is loaded.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|collective-broadcast)"
)
CONTAINERS = ("while", "conditional", "call")  # ops whose time is their body ops' time

Span = Tuple[str, float, float]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Ops:
    """One chip's op events: ``names[ids[i]]`` ran over [start[i], end[i]) ns."""

    names: List[str]  # short HLO names
    ids: np.ndarray
    start: np.ndarray
    end: np.ndarray
    is_async: np.ndarray  # event came from the async line
    labels: Optional[List[str]] = None  # breakdown labels, by id; the names when absent

    @classmethod
    def from_events(cls, events: Sequence[Tuple[str, float, float]], asynchronous=()):
        names = sorted({n for n, _, _ in events} | {n for n, _, _ in asynchronous})
        index = {n: i for i, n in enumerate(names)}
        evs = list(events) + list(asynchronous)
        return cls(names, np.array([index[n] for n, _, _ in evs], dtype=np.int64),
                   np.array([s for _, s, _ in evs], dtype=np.float64),
                   np.array([e for _, _, e in evs], dtype=np.float64),
                   np.array([False] * len(events) + [True] * len(asynchronous)))


def short_name(hlo: str) -> str:
    """``"%fusion.12 = f32[8] fusion(...)"`` -> ``"fusion.12"``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def op_label(hlo: str) -> str:
    """The op's short name, result type and fusion kind, for the breakdown:
    ``"fusion.12 f32[8,16] kLoop"``."""
    head, _, rest = hlo.partition(" = ")
    result = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0])[:60] if rest else ""
    if result.startswith("("):
        result = "tuple"
    kind = re.search(r"kind=(k\w+)", rest)
    return " ".join(x for x in (short_name(head), result, kind.group(1) if kind else "") if x)


def _schema():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_chip_xplane.proto", package="bench_chip")

    def msg(name, fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".bench_chip." + tname

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("XEventMetadata", [("id", 1, F.TYPE_INT64, one, None), ("name", 2, F.TYPE_STRING, one, None)])
    msg("MetadataEntry", [("key", 1, F.TYPE_INT64, one, None),
                          ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata")])
    msg("XEvent", [("metadata_id", 1, F.TYPE_INT64, one, None), ("offset_ps", 2, F.TYPE_INT64, one, None),
                   ("duration_ps", 3, F.TYPE_INT64, one, None)])
    msg("XLine", [("name", 2, F.TYPE_STRING, one, None), ("timestamp_ns", 3, F.TYPE_INT64, one, None),
                  ("events", 4, F.TYPE_MESSAGE, rep, "XEvent")])
    msg("XPlane", [("name", 2, F.TYPE_STRING, one, None), ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
                   ("event_metadata", 4, F.TYPE_MESSAGE, rep, "MetadataEntry")])
    msg("XSpace", [("planes", 1, F.TYPE_MESSAGE, rep, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_chip.XSpace"))


def find_xspace(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _line_arrays(line):
    n = len(line.events)
    ids = np.fromiter((e.metadata_id for e in line.events), np.int64, n)
    off = np.fromiter((e.offset_ps for e in line.events), np.float64, n)
    dur = np.fromiter((e.duration_ps for e in line.events), np.float64, n)
    start = line.timestamp_ns + off * 1e-3
    return ids, start, start + dur * 1e-3


def load(path: str) -> Tuple[Dict[int, Ops], List[Span]]:
    """({chip id: its ops}, the benchmark's host spans) of one trace file."""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    chips: Dict[int, Ops] = {}
    spans: List[Span] = []
    for plane in space.planes:
        meta = {e.key: e.value.name for e in plane.event_metadata}
        m = DEVICE_PLANE.match(plane.name)
        if m:
            keys = sorted(meta)
            index = {k: i for i, k in enumerate(keys)}
            lookup = np.vectorize(lambda k: index.get(k, -1), otypes=[np.int64])
            parts = []
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE) and len(line.events):
                    ids, s, e = _line_arrays(line)
                    parts.append((lookup(ids), s, e, np.full(len(ids), line.name == ASYNC_LINE)))
            if parts:
                chips[int(m.group(1))] = Ops([short_name(meta[k]) for k in keys],
                                             *[np.concatenate(x) for x in zip(*parts)],
                                             labels=[op_label(meta[k]) for k in keys])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = meta.get(e.metadata_id, "")
                    if name.startswith(SPAN_PREFIX):
                        s = line.timestamp_ns + e.offset_ps * 1e-3
                        spans.append((name, s, s + e.duration_ps * 1e-3))
    return chips, spans


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The union of intervals as sorted, disjoint (starts, ends)."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


def gaps(bs: np.ndarray, be: np.ndarray, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of sorted, disjoint busy intervals within [lo, hi]."""
    edges_s = np.concatenate([[lo], be])
    edges_e = np.concatenate([bs, [hi]])
    keep = edges_e > edges_s
    return list(zip(edges_s[keep].tolist(), edges_e[keep].tolist()))


def _label(gap: Tuple[float, float], spans: Sequence[Span]) -> str:
    best, most = "no benchmark span", 0.0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > most:
            best, most = name, ov
    return best


def reduce(chips: Dict[int, Ops], spans: Sequence[Span], n_chips: int, top: int = 10) -> Optional[dict]:
    """Device metrics over the last ``bench/window`` span for the first
    ``n_chips`` chips; None when the trace holds no window or no op there."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    ids = sorted(chips)[:n_chips]
    if not windows or not ids:
        return None
    lo, hi = windows[-1]
    busy, coll, n_coll = [], [], 0
    per_op: Dict[str, float] = {}
    chip0_gaps: List[Tuple[float, float]] = []
    for i in ids:
        ops = chips[i]
        inside = (ops.end > lo) & (ops.start < hi)
        s, e = np.clip(ops.start[inside], lo, hi), np.clip(ops.end[inside], lo, hi)
        op_ids, asyn = ops.ids[inside], ops.is_async[inside]
        sync = ~asyn
        bs, be = union(s[sync], e[sync])
        busy.append(float(np.sum(be - bs)))
        is_coll = np.array([bool(COLLECTIVE.match(n)) for n in ops.names] + [False])[op_ids]
        cs, ce = union(s[is_coll], e[is_coll])
        coll.append(float(np.sum(ce - cs)))
        n_coll += int(np.sum(is_coll & sync))
        stems = [n.split(".")[0] for n in ops.names]
        totals = np.bincount(op_ids[sync] + 1, weights=(e - s)[sync], minlength=len(ops.names) + 1)
        labels = ops.labels or ops.names
        for k, t in enumerate(totals[1:]):
            if t > 0 and stems[k] not in CONTAINERS:
                per_op[labels[k]] = per_op.get(labels[k], 0.0) + t / len(ids)
        if i == ids[0]:
            chip0_gaps = gaps(bs, be, lo, hi)
    if not any(busy):
        return None
    longest = sorted(chip0_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(ids) * 1e-9,
        "collective_s": sum(coll) / len(ids) * 1e-9,
        "collective_ops": n_coll,
        "device_ops": [[n, t * 1e-9] for n, t in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * 1e-9] for g in longest],
    }
