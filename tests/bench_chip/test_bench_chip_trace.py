"""The reduction from a profiler trace to device metrics: on hand-made
events, and on small traces recorded on the chip and committed beside the
benchmark (``benchmarks/chip/testdata``), against jaxlib's own reader."""

import os

import numpy as np
import pytest

from bench_chip_helpers import ROOT
from benchmarks.chip import trace_reduce as tr

TESTDATA = os.path.join(ROOT, "benchmarks", "chip", "testdata")


def test_union_and_gaps():
    bs, be = tr.union(np.array([30.0, 0, 5, 40]), np.array([40.0, 10, 20, 45]))
    assert bs.tolist() == [0, 30] and be.tolist() == [20, 45]
    assert tr.gaps(bs, be, -5, 50) == [(-5, 0), (20, 30), (45, 50)]


def test_short_names():
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %all-reduce.3)") == "fusion.12"
    assert not tr.COLLECTIVE.match(tr.short_name("%fusion.12 = f32[8] fusion(%all-reduce.3)"))
    assert tr.COLLECTIVE.match("collective-permute-start.2")


def test_reduce_on_hand_made_events():
    chips = {
        0: tr.Ops.from_events(
            [("while.1", 100, 1200), ("fusion.1", 100, 300), ("collective-permute-start.2", 300, 350),
             ("all-reduce.3", 500, 600), ("fusion.1", 900, 1200)],
            asynchronous=[("collective-permute-done.2", 320, 420)]),
        1: tr.Ops.from_events([("fusion.1", 100, 600), ("all-gather.7", 600, 700)]),
        2: tr.Ops.from_events([("fusion.9", 0, 1000)]),  # a chip the cell does not use
    }
    spans = [("bench/window", 0, 1000), ("bench/data_wait", 350, 480), ("bench/wait", 600, 1000)]
    r = tr.reduce(chips, spans, n_chips=2)
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy 100-1000 (the while loop spans its body) = 900; chip 1 100-700 = 600
    assert abs(r["busy_s"] - 750e-9) < 1e-15
    # collectives: chip 0 300-420 and 500-600 = 220, chip 1 600-700 = 100
    assert abs(r["collective_s"] - 160e-9) < 1e-15
    assert r["collective_ops"] == 3
    assert [n for n, _ in r["device_ops"]][:1] == ["fusion.1"]  # while loops are left out
    assert [(n, pytest.approx(t)) for n, t in r["idle_gaps"]] == [("no benchmark span", 100e-9)]
    chips[0] = tr.Ops.from_events([("fusion.1", 100, 300), ("fusion.2", 500, 600)])
    r = tr.reduce(chips, spans, n_chips=1)
    assert [(n, pytest.approx(t)) for n, t in r["idle_gaps"][:2]] == [
        ("bench/wait", 400e-9), ("bench/data_wait", 200e-9)]
    assert tr.reduce(chips, [], n_chips=2) is None


@pytest.mark.parametrize("name,n_chips", [("small_1chip.xplane.pb", 1)])
def test_reduce_on_a_recorded_chip_trace(name, n_chips):
    from jax._src.profiler import ProfileData

    path = os.path.join(TESTDATA, name)
    chips, spans = tr.load(path)
    r = tr.reduce(chips, spans, n_chips=n_chips)
    assert sorted(chips) == list(range(n_chips))
    # busy and window again, straight from jaxlib's reader
    window, busy = None, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == tr.HOST_PLANE:
                for e in line.events:
                    if e.name == tr.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            ivs = sorted((max(e.start_ns, window[0]), min(e.start_ns + e.duration_ns, window[1]))
                         for line in plane.lines if line.name == tr.OPS_LINE for e in line.events
                         if e.start_ns + e.duration_ns > window[0] and e.start_ns < window[1])
            total, end = 0.0, -1.0
            for s, e in ivs:
                total += max(0.0, e - max(s, end))
                end = max(end, e)
            busy.append(total)
    assert r["window_s"] == pytest.approx((window[1] - window[0]) * 1e-9, rel=1e-6)
    # jaxlib rounds to whole nanoseconds; the reduction keeps picoseconds
    assert r["busy_s"] == pytest.approx(sum(busy) / len(busy) * 1e-9, rel=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    assert (r["collective_ops"] > 0) == (n_chips > 1)
    assert {label for label, _ in r["idle_gaps"]} <= {
        "bench/dispatch", "bench/wait", "no benchmark span"}
