"""Device time under the Mamba-2 mixer's named scopes, read from the
traced segment's op names as ``scopes.py`` reads the step's layer scopes.

``models/ssm._mamba2_mix`` puts ``jax.named_scope("ssm")`` over the whole
mixer (in_proj, conv, SSD, gated norm, out_proj) and a nested ``ssd`` over
the chunked scan.  An op on ``XLA Ops`` or ``Async XLA Ops`` (bar the
containers of ``trace_reduce.CONTAINERS``) is under a scope when the scope
is a whole ``/`` segment of its ``tf_op``.  Per chip, over the last
``bench/window`` span, per traced step: ``ssm_ms`` and ``ssd_ms``, the
union of those ops' intervals, each the mean over the cell's chips; None
where no op carries the scope, as in a program without it.  The names are
copied here, not imported from the program.

The readers' ``ctx`` carries no configuration: a reader takes its cell's
from the one cell its own ``BENCHMARK.json`` entry lists (``cell_of``).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.chip import harness, scopes
from benchmarks.chip import trace_reduce as tr

SCOPES = ("ssm", "ssd")


def load(path: str) -> Tuple[Dict[int, Tuple[np.ndarray, ...]], List[tr.Span]]:
    """({chip id: (in ssm, in ssd, start ns, end ns) of its ops}, the host's
    ``bench/window`` spans) of one trace file."""
    space = scopes._schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    chips: Dict[int, Tuple[np.ndarray, ...]] = {}
    windows: List[tr.Span] = []
    for plane in space.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            index = {e.key: i for i, e in enumerate(plane.event_metadata)}
            rows = []
            for e in plane.event_metadata:
                segments = set(scopes._tf_op(e.value, stat_names).split("/"))
                keep = tr.short_name(e.value.name).split(".")[0] not in tr.CONTAINERS
                rows.append(tuple(keep and s in segments for s in SCOPES))
            # one row per metadata entry, and a last one for an event whose entry is missing
            table = np.array(rows + [(False,) * len(SCOPES)], dtype=bool).reshape(-1, len(SCOPES))
            parts = []
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, tr.ASYNC_LINE) and len(line.events):
                    ids, s, e = tr._line_arrays(line)
                    flags = table[[index.get(int(i), -1) for i in ids]]
                    parts.append((flags, s, e))
            if parts:
                chips[int(m.group(1))] = (np.concatenate([p[0] for p in parts]),
                                          np.concatenate([p[1] for p in parts]),
                                          np.concatenate([p[2] for p in parts]))
        elif plane.name == tr.HOST_PLANE:
            meta = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for e in line.events:
                    if meta.get(e.metadata_id, "") == tr.WINDOW_SPAN:
                        s = line.timestamp_ns + e.offset_ps * 1e-3
                        windows.append((tr.WINDOW_SPAN, s, s + e.duration_ps * 1e-3))
    return chips, windows


def reduce(chips, windows, n_chips: int, steps: int) -> Optional[dict]:
    """``{"window_s", "ssm_ms", "ssd_ms"}`` over the last window for the
    first ``n_chips`` chips, per step of the ``steps`` traced; None without
    a window or a chip."""
    ids = sorted(chips)[:n_chips]
    if not windows or not ids:
        return None
    _, lo, hi = windows[-1]
    total = np.zeros(len(SCOPES))
    seen = np.zeros(len(SCOPES), bool)
    for i in ids:
        flags, start, end = chips[i]
        inside = (end > lo) & (start < hi)
        s, e = np.clip(start[inside], lo, hi), np.clip(end[inside], lo, hi)
        for k in range(len(SCOPES)):
            mine = flags[inside, k]
            seen[k] |= bool(mine.any())
            u0, u1 = tr.union(s[mine], e[mine])
            total[k] += float(np.sum(u1 - u0))
    out = {"window_s": (hi - lo) * 1e-9}
    for k, name in enumerate(SCOPES):
        out[name + "_ms"] = float(total[k] * 1e-6 / (len(ids) * steps)) if seen[k] else None
    return out


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime: float, n_chips: int, steps: int) -> Optional[dict]:
    return reduce(*load(path), n_chips=n_chips, steps=steps)


def reading(ctx: dict, name: str) -> Optional[float]:
    """The traced run's ``ssm_ms`` or ``ssd_ms``, or None: the newest trace
    under ``harness.TRACE_DIR``, if its window is the run's own."""
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


def cell_of(metric: str, bench_path: str = os.path.join(harness.ROOT, "BENCHMARK.json")) -> harness.Cell:
    """The one cell that ``metric``'s ``BENCHMARK.json`` entry lists."""
    with open(bench_path) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == metric)
    (workload,) = entry["workloads"]
    return harness.load_cell(workload, bench_path)
