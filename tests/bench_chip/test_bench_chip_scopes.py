"""The program's named scopes and the benchmark's reading of them.

The train step's ``jax.named_scope`` names reach the compiled ops of the
one-chip steps and of the four-device Hoplite chain step (in a child
process, which sets the host device count before it imports jax), whole
and never two on one op, also where the compile cache holds the same step
compiled without them; the benchmark keeps the same names; and
``scopes.py`` reduces hand-made events, and a small trace recorded on the
chip (``record_scoped_trace.py``) as jaxlib's and XProf's readers read it,
to per-scope time, exposed sync time, launches and produce spans."""

import json
import math
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench_chip_helpers import harness, tiny_cell
from bench_chip_scope_helpers import compiled_op_names
from benchmarks.chip import scopes
from benchmarks.chip import trace_reduce as tr
from repro.train.step import SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))


def scope_report(names):
    """{scope: the kinds of op it appears on}, and the op names carrying two scopes."""
    seen = {s: set() for s in SCOPES}
    two = []
    for n in names:
        segments = n.split("/")
        hit = [s for s in SCOPES if s in segments]
        if len(hit) > 1:
            two.append(n)
        for s in hit:
            seen[s].add("backward" if any(x.startswith("transpose(") for x in segments) else "forward")
    return {s: sorted(k) for s, k in seen.items()}, two


@pytest.mark.parametrize("workload", ["whisper-medium.train.1chip", "starcoder2-3b-l6.train.1chip"])
def test_scopes_reach_the_compiled_one_chip_step(workload):
    seen, two = scope_report(compiled_op_names(tiny_cell(workload)))
    assert two == []
    assert seen["attention"] == seen["mlp"] == ["backward", "forward"]
    assert seen["optimizer"] == ["forward"]  # it runs on the gradient: no backward of its own
    assert seen["grad_sync"] == []  # one chip: no sync


CHILD = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {here!r})
    from bench_chip_helpers import tiny_cell
    from bench_chip_scope_helpers import compiled_op_names

    cell = tiny_cell("whisper-medium.train.1chip", traffic="hoplite-chain-b8-s448", chips=4)
    print(json.dumps(compiled_op_names(cell)))
    """
)


def test_scopes_reach_the_compiled_four_device_chain_step():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(here=HERE)],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    names = json.loads(proc.stdout.strip().splitlines()[-1])
    seen, two = scope_report(names)
    assert two == []
    assert seen["attention"] == seen["mlp"] == ["backward", "forward"]
    assert seen["optimizer"] == seen["grad_sync"] == ["forward"]
    sync_ops = [n for n in names if "grad_sync" in n.split("/")]
    assert any(n.endswith("/ppermute") for n in sync_ops)  # the chain's permutes
    assert any(n.endswith("/select_n") for n in sync_ops)  # and its chunk updates (jnp.where)


CACHED_CHILD = textwrap.dedent(
    """
    import contextlib, json, sys
    sys.path.insert(0, {here!r})
    import jax

    if {unscoped!r}:  # the same program without its named scopes
        class NoScope(contextlib.ContextDecorator):
            def __init__(self, name):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        jax.named_scope = NoScope
    from bench_chip_helpers import harness, tiny_cell
    from bench_chip_scope_helpers import compiled_op_names

    harness.enable_compile_cache()
    print(json.dumps(compiled_op_names(tiny_cell("starcoder2-3b-l6.train.1chip"))))
    """
)


def test_scopes_survive_a_compile_cache_filled_without_them(tmp_path):
    """The persistent compile cache leaves metadata out of its key unless
    told otherwise: a step compiled without scopes must not come back for
    the step with them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    seen = []
    for unscoped in (True, False):
        proc = subprocess.run([sys.executable, "-c", CACHED_CHILD.format(here=HERE, unscoped=unscoped)],
                              capture_output=True, text=True, timeout=600, env=env)
        assert proc.returncode == 0, proc.stderr[-3000:]
        seen.append(scope_report(json.loads(proc.stdout.strip().splitlines()[-1]))[0])
        assert os.listdir(tmp_path)  # the first compile filled the cache
    assert not any(seen[0].values())
    assert seen[1]["attention"] == seen[1]["mlp"] == ["backward", "forward"]
    assert seen[1]["optimizer"] == ["forward"]


def test_benchmark_keeps_the_programs_scope_names():
    assert scopes.SCOPES == SCOPES
    assert set(scopes.METRIC) == set(SCOPES)


FWD = "jit(train_step)/jvp()/while/body/closed_call/{}/dot_general"
BWD = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/{}/add_any"
SYNC = "jit(train_step)/shard_map/grad_sync/while/body/{}"


def _chips():
    ops = scopes.ChipOps.from_events
    return {
        0: ops(
            [("%while.1 = (f32[8]) while(...)", "jit(train_step)/while", 0, 1000),  # a container
             ("fusion.6", FWD.format("attention"), -100, 50),  # clipped to the window
             ("fusion.1", FWD.format("attention"), 100, 300),
             ("fusion.2", FWD.format("mlp"), 300, 400),
             ("collective-permute-start.1", SYNC.format("ppermute"), 400, 410),
             ("fusion.4", SYNC.format("dynamic_update_slice"), 410, 500),
             ("collective-permute-done.1", SYNC.format("ppermute"), 500, 520),
             ("fusion.5", BWD.format("attention"), 520, 600),
             ("all-reduce.2", "jit(train_step)/psum", 600, 650),  # a collective outside the sync
             ("fusion.3", "jit(train_step)/optimizer/mul", 800, 900)],
            # the permute in flight while the backward's attention runs
            asynchronous=[("collective-permute-start.1", SYNC.format("ppermute"), 400, 700)]),
        1: ops([("all-reduce-start.3", SYNC.format("psum"), 0, 40),
                ("all-reduce-done.3", SYNC.format("psum"), 40, 60),
                ("collective-permute.4", SYNC.format("ppermute"), 60, 100)]),
        2: ops([("fusion.9", SYNC.format("add"), 0, 1000)]),  # a chip the cell does not use
    }


SPANS = [("bench/window", -5000, -4000), ("bench/window", 0, 1000),
         ("data/produce", -300, -200), ("data/produce", -50, 20), ("data/produce", 500, 530),
         ("data/produce", 900, 1100)]


def test_reduce_on_hand_made_events():
    r = scopes.reduce(_chips(), SPANS, n_chips=2, steps=1)
    ms = 1e-6  # per ns
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0: attention 0-50, 100-300, 520-600; chip 1 none
    assert r["attention_ms"] == pytest.approx(330 / 2 * ms)
    assert r["mlp_ms"] == pytest.approx(100 / 2 * ms)
    assert r["optimizer_ms"] == pytest.approx(100 / 2 * ms)
    # sync: chip 0 400-700 (the async line included), chip 1 0-100
    assert r["sync_ms"] == pytest.approx(400 / 2 * ms)
    # exposed: chip 0 400-520 and 650-700 (attention and the outside all-reduce hide the rest)
    assert r["sync_exposed_ms"] == pytest.approx(270 / 2 * ms)
    assert r["sync_exposed_ms"] < r["sync_ms"]
    # launches: one permute start on chip 0; an all-reduce start and a permute on chip 1
    assert r["sync_launches"] == 1.5
    # produce spans that end inside the window, at their whole length: 70 and 30
    assert r["data_produce_ms"] == pytest.approx(50 * ms)
    two_steps = scopes.reduce(_chips(), SPANS, n_chips=2, steps=2)
    assert two_steps["sync_ms"] == pytest.approx(r["sync_ms"] / 2)
    assert two_steps["sync_launches"] == r["sync_launches"] / 2


def test_reduce_without_scopes_reads_nothing():
    chips = {0: scopes.ChipOps.from_events([("fusion.1", "jit(body)/dot_general:", 0, 500),
                                            ("collective-permute.1", "jit(body)/ppermute", 500, 600)])}
    r = scopes.reduce(chips, SPANS[:2], n_chips=1, steps=2)
    assert {k for k, v in r.items() if v is not None} == {"window_s"}
    assert scopes.reduce(chips, [], n_chips=1, steps=2) is None


def test_scope_is_a_whole_segment():
    assert scopes.scope_of(FWD.format("mlp")) == SCOPES.index("mlp")
    assert scopes.scope_of("jit(train_step)/jvp(mlp)/tanh") == -1
    assert scopes.scope_of("jit(train_step)/mlp_head/dot_general") == -1
    assert scopes.scope_of("") == -1


def test_reading_needs_the_runs_own_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    ctx = {"trace": {"window_s": 1e-6}, "chips": 1, "trace_steps": 2}
    assert scopes.reading(ctx, "attention_ms") is None  # no trace file
    assert scopes.reading(dict(ctx, trace=None), "attention_ms") is None


SCOPED_TRACE = os.path.join(harness.HERE, "testdata", "small_scoped_1chip.xplane.pb")


def _independent_reading(path, steps):
    """Per-scope ms per step, the events counted in each, and the mean produce
    span, from jaxlib's reader (times) and XProf's trace viewer (each op's
    ``tf_op``, by its HLO text)."""
    from jax._src.profiler import ProfileData
    from xprof.convert import raw_to_tool_data

    viewer, _ = raw_to_tool_data.xspace_to_tool_data([path], "trace_viewer", {})
    tf_op = {e["args"].get("long_name", e["name"]): e["args"]["tf_op"]
             for e in json.loads(viewer)["traceEvents"] if "tf_op" in e.get("args", {})}
    planes = list(ProfileData.from_file(path).planes)
    host = [e for p in planes if p.name == "/host:CPU" for line in p.lines for e in line.events]
    (lo, hi) = [(e.start_ns, e.start_ns + e.duration_ns) for e in host if e.name == "bench/window"][-1]
    per_scope = {s: [] for s in SCOPES}
    for p in planes:
        if p.name.startswith("/device:TPU:"):
            for line in p.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                for e in line.events:
                    stem = e.name.split(" = ")[0].lstrip("%").split(".")[0]
                    segments = tf_op.get(e.name, "").split("/")
                    for s in SCOPES:
                        if s in segments and stem not in ("while", "conditional", "call"):
                            per_scope[s].append((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)))
    out, count = {}, {}
    for s, ivs in per_scope.items():
        total, end = 0.0, -math.inf
        for a, b in sorted(iv for iv in ivs if iv[1] > iv[0]):
            total += max(0.0, b - max(a, end))
            end = max(end, b)
        out[s] = total / steps * 1e-6 if ivs else None
        count[s] = len(ivs)
    produced = [e.duration_ns for e in host if e.name == "data/produce" and lo < e.start_ns + e.duration_ns <= hi]
    return out, count, sum(produced) / len(produced) * 1e-6


def test_scopes_on_a_recorded_chip_trace(tmp_path, monkeypatch):
    chips, spans = scopes.load(SCOPED_TRACE)
    r = scopes.reduce(chips, spans, n_chips=1, steps=2)
    want, count, produce_ms = _independent_reading(SCOPED_TRACE, steps=2)
    for s in ("attention", "mlp", "optimizer"):
        # jaxlib rounds each event to whole nanoseconds (these ops last a few);
        # the reduction keeps picoseconds
        assert r[scopes.METRIC[s]] == pytest.approx(want[s], abs=count[s] * 1e-6 / 2), s
    assert r["sync_ms"] is want["grad_sync"] is None  # one chip: no sync
    assert r["data_produce_ms"] == pytest.approx(produce_ms, rel=1e-3)
    busy = tr.reduce(*tr.load(SCOPED_TRACE), n_chips=1)["busy_s"] / 2 * 1e3
    assert 0 < r["attention_ms"] + r["mlp_ms"] + r["optimizer_ms"] <= busy
    # read as a run reads it: the newest trace under the harness's directory
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    shutil.copy(SCOPED_TRACE, tmp_path / "run.xplane.pb")
    ctx = {"trace": {"window_s": r["window_s"]}, "chips": 1, "trace_steps": 2}
    assert scopes.reading(ctx, "mlp_ms") == r["mlp_ms"]
    # another run's window: nothing read
    ctx["trace"]["window_s"] += 2e-9
    assert scopes.reading(ctx, "mlp_ms") is None
