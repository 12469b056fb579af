"""Fault-injection plane + bounded-time collectives.

Covers the FaultToleranceConfig threading, the FaultInjector's pure
deterministic penalty math, bounded-time (k-of-n) allreduce with
straggler cuts, and the stall-budget eviction paths: a Get whose source
watermark wedges re-plans onto another replica, and a reduce-chain fold
whose upstream partial wedges re-splices from a late-published copy.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.api import SUM, ObjectLost
from repro.core.faults import (
    FaultInjector,
    FaultPlan,
    FaultToleranceConfig,
    LinkFault,
    StragglerSpec,
)
from repro.core.local import AllreduceResult, LocalCluster
from repro.core.planner import bounded_time_participants

ELEMS = 40_000  # 320 KB of float64 -- well past the 64 KB inline threshold


def _vals(n, elems=ELEMS):
    return [np.random.RandomState(100 + i).rand(elems) for i in range(n)]


# -- configuration threading --------------------------------------------------


def test_fault_tolerance_config_threads_through_cluster():
    ft = FaultToleranceConfig(
        stall_timeout=1.5, watermark_recheck_s=0.5, get_timeout=7.0,
        reduce_timeout=9.0, join_timeout=3.0,
    )
    c = LocalCluster(2, fault_tolerance=ft)
    assert c.ft is ft
    assert c.stall_timeout == 1.5  # back-compat alias

    # The legacy kwarg overrides just the stall budget.
    c2 = LocalCluster(2, fault_tolerance=ft, stall_timeout=0.25)
    assert c2.ft.stall_timeout == 0.25
    assert c2.ft.get_timeout == 7.0

    # Defaults apply when nothing is passed.
    c3 = LocalCluster(2)
    assert c3.ft == FaultToleranceConfig()


def test_fault_plan_coerced_to_injector():
    plan = FaultPlan(seed=3, stragglers=[StragglerSpec(node=1, factor=2.0)])
    c = LocalCluster(2, faults=plan)
    assert isinstance(c.faults, FaultInjector)
    assert c.faults.plan is plan


def test_bounded_time_participants():
    assert bounded_time_participants(8) == 7
    assert bounded_time_participants(8, 5) == 5
    assert bounded_time_participants(8, 0) == 1  # clamped to >= 1
    assert bounded_time_participants(8, 99) == 8  # clamped to <= n
    assert bounded_time_participants(1) == 1


# -- injector determinism + penalty math --------------------------------------


def test_storm_plan_is_seed_deterministic():
    a = FaultPlan.storm(7, 8)
    b = FaultPlan.storm(7, 8)
    assert a == b
    assert FaultPlan.storm(8, 8) != a
    ia, ib = FaultInjector(a), FaultInjector(b)
    assert ia.timeline() == ib.timeline()
    # Pure draws: identical grids for identical (seed, src, dst, k).
    grid_a = [ia.chunk_factors(s, d, k) for s in range(4) for d in range(4) for k in range(8)]
    grid_b = [ib.chunk_factors(s, d, k) for s in range(4) for d in range(4) for k in range(8)]
    assert grid_a == grid_b


def test_window_penalty_math():
    # Bandwidth halved: a window that takes base seconds clean takes
    # 2*base degraded -- penalty == base exactly, no jitter configured.
    plan = FaultPlan(seed=0, link_faults=[LinkFault(bandwidth_factor=0.5)],
                     compute_jitter=0.0)
    inj = FaultInjector(plan)
    assert inj.window_penalty(0, 1, 0, 0.01) == pytest.approx(0.01)

    # A 4x straggler's outbound link serves each window 4x slower.
    plan2 = FaultPlan(seed=0, stragglers=[StragglerSpec(node=2, factor=4.0)],
                      compute_jitter=0.0)
    inj2 = FaultInjector(plan2)
    assert inj2.window_penalty(2, 0, 0, 0.01) == pytest.approx(0.03)
    assert inj2.window_penalty(0, 1, 0, 0.01) == 0.0  # untouched link

    # Link filters apply only to matching (src, dst) pairs.
    plan3 = FaultPlan(seed=0, link_faults=[LinkFault(src=1, dst=2, jitter_s=0.005)])
    inj3 = FaultInjector(plan3)
    assert inj3.window_penalty(1, 2, 0, 0.01) > 0.0
    assert inj3.window_penalty(2, 1, 0, 0.01) == 0.0


def test_compute_delay_straggler_and_determinism():
    plan = FaultPlan(seed=11, stragglers=[StragglerSpec(node=3, factor=4.0)],
                     compute_jitter=0.2)
    inj = FaultInjector(plan)
    # Straggler multiplies base compute; healthy nodes see only jitter.
    assert inj.compute_delay(3, 1.0) >= 4.0
    assert inj.compute_delay(0, 1.0) < 4.0
    assert inj.compute_delay(0, 1.0, k=5) == FaultInjector(plan).compute_delay(0, 1.0, k=5)


# -- bounded-time allreduce ---------------------------------------------------


def test_bounded_allreduce_no_cut_when_all_ready():
    c = LocalCluster(4)
    vals = _vals(4)
    for i in range(4):
        c.put(i, f"g{i}", vals[i])
    res = c.allreduce([0, 1, 2, 3], "sum", [f"g{i}" for i in range(4)],
                      deadline=5.0, min_participants=3)
    assert isinstance(res, AllreduceResult)
    assert res == "sum"  # still usable as the plain object id
    assert res.cut is False
    assert res.mask == (True, True, True, True)
    assert res.dropped == ()
    expect = sum(vals)
    for n in range(4):
        np.testing.assert_allclose(c.get(n, "sum"), expect, rtol=1e-10)


def test_bounded_allreduce_cuts_straggler():
    c = LocalCluster(4, trace=True)
    vals = _vals(4)
    for i in range(3):
        c.put(i, f"g{i}", vals[i])
    # g3 arrives far too late: the cut must fire at the soft deadline.
    t = threading.Timer(3.0, lambda: c.put(3, "g3", vals[3]))
    t.daemon = True
    t.start()
    t0 = time.time()
    res = c.allreduce([0, 1, 2, 3], "sum", [f"g{i}" for i in range(4)],
                      deadline=0.3, min_participants=3)
    wall = time.time() - t0
    t.cancel()
    assert wall < 2.5, f"cut did not bound the collective ({wall:.2f}s)"
    assert res.cut is True
    assert res.mask == (True, True, True, False)
    assert res.dropped == ("g3",)
    assert res.participants == ("g0", "g1", "g2")
    stats = c.stats
    assert stats["straggler_cuts"] == 1
    assert stats["dropped_contributions"] == 1
    assert any(e[4] == "straggler-cut" for e in c.trace.events())
    # Partial fold: exactly the sum of the kept contributions.
    expect = vals[0] + vals[1] + vals[2]
    for n in range(3):
        np.testing.assert_allclose(c.get(n, "sum"), expect, rtol=1e-10)


def test_bounded_allreduce_quorum_all_blocks_until_arrival():
    # min_participants == n degenerates to the unbounded semantics: the
    # cut can never drop anyone, so a missing source times out.
    c = LocalCluster(3)
    vals = _vals(3)
    for i in range(2):
        c.put(i, f"g{i}", vals[i])
    with pytest.raises(TimeoutError):
        c.allreduce([0, 1, 2], "sum", ["g0", "g1", "g2"],
                    deadline=0.2, min_participants=3, timeout=1.0)


def test_bounded_allreduce_lost_below_quorum_raises():
    c = LocalCluster(3)
    vals = _vals(3)
    for i in range(3):
        c.put(i, f"g{i}", vals[i])
    c.fail_node(1)  # g1's only copy dies -> only 2 sources can ever arrive
    with pytest.raises(ObjectLost):
        c.allreduce([0, 2], "sum", ["g0", "g1", "g2"],
                    deadline=0.2, min_participants=3, timeout=2.0)


def test_bounded_allreduce_deadline_none_folds_at_quorum():
    # deadline=None + min_participants: fold the moment k are ready,
    # no grace period for the missing source.
    c = LocalCluster(3)
    vals = _vals(3)
    c.put(0, "g0", vals[0])
    c.put(1, "g1", vals[1])
    t0 = time.time()
    res = c.allreduce([0, 1, 2], "sum", ["g0", "g1", "g2"],
                      min_participants=2, timeout=10.0)
    assert time.time() - t0 < 2.0
    assert res.cut and res.dropped == ("g2",)
    np.testing.assert_allclose(c.get(0, "sum"), vals[0] + vals[1], rtol=1e-10)


def test_partial_fold_scale():
    from repro.core.planner import partial_fold_scale

    assert partial_fold_scale((True, True, True, False)) == pytest.approx(4 / 3)
    assert partial_fold_scale((True,) * 8) == 1.0
    with pytest.raises(ValueError):
        partial_fold_scale((False, False))


# -- stall-budget eviction (acceptance) ---------------------------------------


def test_stalled_fetch_replans_onto_faster_replica():
    """A Get streaming from a wedged partial must evict it within the
    stall budget and resume (not restart) from another copy -- well
    before its own deadline."""
    ft = FaultToleranceConfig(stall_timeout=0.3, watermark_recheck_s=0.1,
                              get_timeout=30.0)
    c = LocalCluster(3, chunk_size=4096, pace=0.002, max_out_degree=1,
                     fault_tolerance=ft, trace=True)
    x = np.random.RandomState(0).rand(ELEMS)
    c.put(0, "x", x)
    size = x.nbytes
    half = (size // 2) - ((size // 2) % 4096)

    # Manufacture a wedged in-flight copy at node 1: the real prefix
    # bytes landed, then the "sender" died silently -- watermark frozen.
    raw = np.frombuffer(x.tobytes(), dtype=np.uint8)
    wedged = c.stores[1].create("x", size, pinned=False, chunk_size=4096)
    wedged.write_chunk(0, raw[:half])
    with c.lock:
        c.directory.publish_partial("x", 1, size)
        c.directory.update_progress("x", 1, half)
        # Saturate node 0's outbound cap so planning must pick the
        # wedged partial first (it leads node 2's zero progress).
        epoch0 = c.directory.charge_source("x", 0)

    # Free the complete copy shortly after the stall budget expires --
    # the re-plan should land on it and resume from the half watermark.
    def free():
        with c.lock:
            c.directory.release_source("x", 0, epoch0)

    t = threading.Timer(0.8, free)
    t.daemon = True
    t.start()

    t0 = time.time()
    got = c.get(2, "x", timeout=30.0)
    wall = time.time() - t0
    np.testing.assert_array_equal(got, x)
    assert wall < 5.0, f"stall re-plan rode the deadline ({wall:.2f}s)"
    assert c.stats["stall_replans"] >= 1
    replans = [e for e in c.trace.events()
               if e[4] == "replan" and (e[7] or {}).get("reason") == "source-stalled"]
    assert replans, "no source-stalled replan recorded in the trace"
    assert replans[0][7]["src"] == 1


def test_stalled_fold_input_evicted_and_raspliced():
    """A reduce-chain hop folding from a wedged upstream partial must
    evict it once another live copy appears and re-splice, resuming from
    its own output watermark -- the fold completes exactly."""
    ft = FaultToleranceConfig(stall_timeout=0.3, watermark_recheck_s=0.1)
    c = LocalCluster(4, chunk_size=4096, pace=0.002, fault_tolerance=ft,
                     trace=True)
    rng = np.random.RandomState(1)
    g1, g2 = rng.rand(ELEMS), rng.rand(ELEMS)
    size = g1.nbytes
    half = (size // 2) - ((size // 2) % 4096)

    # g2 is a healthy complete source; g1 exists only as a wedged
    # *producing* partial at node 1 (prefix bytes are the real bytes).
    c.put(2, "g2", g2)
    raw1 = np.frombuffer(g1.tobytes(), dtype=np.uint8)
    wedged = c.stores[1].create("g1", size, pinned=False, chunk_size=4096)
    wedged.write_chunk(0, raw1[:half])
    with c.lock:
        c.meta["g1"] = (g1.dtype, g1.shape)
        c.directory.publish_partial("g1", 1, size, producing=True)
        c.directory.update_progress("g1", 1, half)

    # A full replica of g1 appears elsewhere only after the fold has
    # already wedged on the stalled copy.
    t = threading.Timer(0.5, lambda: c.put(3, "g1", g1))
    t.daemon = True
    t.start()

    t0 = time.time()
    c.reduce(0, "rsum", ["g1", "g2"], SUM, timeout=30.0)
    wall = time.time() - t0
    np.testing.assert_allclose(c.get(0, "rsum"), g1 + g2, rtol=1e-10)
    assert wall < 6.0, f"fold stall rode the reduce deadline ({wall:.2f}s)"
    stats = c.stats
    assert stats["stall_replans"] >= 1
    assert stats["resplices"] >= 1
    replans = [e for e in c.trace.events()
               if e[4] == "replan" and (e[7] or {}).get("reason") == "source-stalled"]
    assert replans, "no source-stalled replan recorded in the trace"
