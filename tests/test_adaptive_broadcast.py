"""Receiver-driven adaptive broadcast: on-the-fly multicast trees from
partial copies, sender load balancing, mid-stream failover with
watermark resume, and the shared broadcast-tree policy."""

import time

import numpy as np
import pytest

from repro.core import planner, scheduler
from repro.core.api import Location, ObjectLost, Progress
from repro.core.directory import ObjectDirectory
from repro.core.local import LocalCluster
from repro.core.planner import EC2_LINK, broadcast_policy


# ---------------------------------------------------------------------------
# policy (planner + scheduler, shared by simulator and LocalCluster)
# ---------------------------------------------------------------------------


def test_broadcast_policy_regimes():
    # Large object: bandwidth-bound -> pipelined tree, small fan-out.
    big = broadcast_policy(15, EC2_LINK, 64 << 20, chunk=4096)
    assert big.strategy == "pipelined"
    assert big.max_out_degree == 1  # shared egress: the paper's rule
    assert broadcast_policy(15, EC2_LINK, 64 << 20, egress_sharing=False).max_out_degree == 2
    # Tiny object: latency-bound -> bushy store-and-forward tree.
    small = broadcast_policy(15, EC2_LINK, 1 << 10, chunk=1 << 10)
    assert small.strategy == "binomial"
    assert small.max_out_degree == 4  # ceil(log2(16))
    assert broadcast_policy(1, EC2_LINK, 1 << 20).max_out_degree == 1


def test_select_source_feasibility_and_load():
    complete = Location(0, Progress.COMPLETE, 100)
    leading = Location(1, Progress.PARTIAL, 60)
    behind = Location(2, Progress.PARTIAL, 10)
    # A copy at or behind the receiver can never feed it.
    got = scheduler.select_source([behind], loads={}, min_lead=10)
    assert got is None
    got = scheduler.select_source([complete, leading, behind], loads={}, min_lead=30)
    assert got.node in (0, 1)
    # Least-loaded wins over complete-preference.
    got = scheduler.select_source(
        [complete, leading], loads={0: 1, 1: 0}, min_lead=0
    )
    assert got.node == 1
    # Out-degree cap filters; all-at-cap -> None (caller waits for a slot).
    got = scheduler.select_source(
        [complete, leading], loads={0: 2, 1: 2}, min_lead=0, max_out_degree=2
    )
    assert got is None
    # served tie-break: the origin sheds repeat requests onto fresh holders.
    c2 = Location(3, Progress.COMPLETE, 100)
    got = scheduler.select_source(
        [complete, c2], loads={}, served={0: 2, 3: 0}, min_lead=0
    )
    assert got.node == 3


def test_directory_select_source_charges_and_releases():
    d = ObjectDirectory()
    d.publish_complete("x", node=0, size=100)
    d.publish_partial("x", node=1, size=100)
    d.update_progress("x", 1, 50)
    a = d.select_source("x", max_out_degree=1)
    b = d.select_source("x", max_out_degree=1, min_lead=10)
    assert {a.node, b.node} == {0, 1}
    assert d.outbound_load(a.node) == 1 and d.outbound_load(b.node) == 1
    assert d.select_source("x", max_out_degree=1) is None  # all at cap
    d.release_source("x", a.node)
    assert d.outbound_load(a.node) == 0
    assert d.select_source("x", max_out_degree=1) is not None


def test_stale_release_after_restart_does_not_free_new_charge():
    """A release from a stream that predates the node's fail/restart must
    not decrement charges belonging to its post-restart streams (review
    finding: out-degree cap invariant broke under fail/restart storms)."""
    d = ObjectDirectory()
    d.publish_complete("x", node=0, size=100)
    assert d.select_source("x").node == 0
    stale_epoch = d.charge_epoch(0)
    assert d.outbound_load(0) == 1
    d.reset_outbound(0)  # node failed/restarted mid-send
    d.publish_complete("x", node=0, size=100)
    assert d.select_source("x").node == 0  # post-restart charge
    assert d.outbound_load(0) == 1
    d.release_source("x", 0, stale_epoch)  # late release from the old stream
    assert d.outbound_load(0) == 1, "stale release freed a live slot"
    d.release_source("x", 0, d.charge_epoch(0))
    assert d.outbound_load(0) == 0


def test_cap_blocked_receiver_woken_by_other_objects_release():
    """The outbound cap is per node across objects: a receiver of object
    b turned away by node 0's cap (busy serving object a) must wake when
    a's transfer releases the slot."""
    d = ObjectDirectory()
    d.publish_complete("a", node=0, size=100)
    d.publish_complete("b", node=0, size=100)
    assert d.select_source("a", max_out_degree=1).node == 0
    assert d.select_source("b", max_out_degree=1) is None  # cap-blocked
    fired = []
    d.subscribe("b", fired.append)
    n = len(fired)  # subscribe fires once for the existing location
    d.release_source("a", 0, d.charge_epoch(0))
    assert len(fired) == n + 1, "freed slot did not wake the blocked object"
    assert d.select_source("b", max_out_degree=1).node == 0


def test_update_progress_wakes_waiting_subscriber_once_feasible():
    d = ObjectDirectory()
    d.publish_partial("x", node=0, size=100)
    fired = []
    d.subscribe("x", fired.append)
    n = len(fired)  # subscribe itself fires for the existing location
    d.update_progress("x", 0, 10)  # 0 -> positive: feasibility event
    assert len(fired) == n + 1
    d.update_progress("x", 0, 20)  # later advances: no wakeup storm
    assert len(fired) == n + 1


# ---------------------------------------------------------------------------
# threaded cluster: tree formation, load caps, failover resume
# ---------------------------------------------------------------------------


def test_origin_serves_out_degree_not_n():
    """16-receiver broadcast: the origin streams at most out-degree
    copies; everything else relays through first-generation receivers."""
    n_recv = 16
    c = LocalCluster(n_recv + 1, chunk_size=64 * 1024, pace=0.0005)
    x = np.random.RandomState(0).rand(100_000).astype(np.float32)
    c.put(0, "x", x)
    futs = [c.get_async(i, "x", timeout=60.0) for i in range(1, n_recv + 1)]
    for f in futs:
        np.testing.assert_array_equal(f.result(timeout=60.0), x)
    cap = c.broadcast_out_degree(x.nbytes)
    served = c.stats["bytes_served"]
    assert served.get(0, 0) <= cap * x.nbytes, served
    assert max(c.stats["peak_outbound"].values()) <= cap


def test_mid_broadcast_source_failure_replans_and_resumes():
    """Kill a partial source while downstream receivers chase its
    watermark: they must re-plan to a surviving copy, resume from their
    own watermark, and deliver byte-identical data in < 2 s."""
    c = LocalCluster(6, chunk_size=32 * 1024, pace=0.002, max_out_degree=4)
    x = np.random.RandomState(1).rand(200_000).astype(np.float32)  # ~25 chunks
    c.put(0, "x", x)
    # Node 1 starts pulling; its partial becomes the preferred source for
    # the chasers (origin sheds load via the served tie-break).
    f1 = c.get_async(1, "x", timeout=30.0)
    deadline = time.time() + 10.0
    while time.time() < deadline:
        buf = c.stores[1].get("x")
        if buf is not None and 0 < buf.bytes_present < buf.size:
            break
        time.sleep(0.001)
    chasers = [c.get_async(i, "x", timeout=30.0) for i in range(2, 6)]
    # Let the chasers latch onto node 1's partial mid-flight.
    deadline = time.time() + 10.0
    while time.time() < deadline:
        if any(c.stores[i].get("x") is not None for i in range(2, 6)):
            break
        time.sleep(0.001)
    t0 = time.time()
    c.fail_node(1)
    for f in chasers:
        got = f.result(timeout=30.0)
        np.testing.assert_array_equal(got, x)  # byte equality, no corruption
    assert time.time() - t0 < 2.0, "failover rode a timeout instead of an event"
    with pytest.raises((ObjectLost, Exception)):
        f1.result(timeout=5.0)  # the killed receiver itself aborts


def test_failover_resumes_from_watermark_not_zero():
    """After the serving copy dies mid-stream the receiver re-plans and
    streams only the REMAINING bytes from the surviving copy."""
    c = LocalCluster(3, chunk_size=32 * 1024, pace=0.002)
    x = np.random.RandomState(2).rand(200_000).astype(np.float32)
    c.put(0, "x", x)
    c.put(2, "x", x)  # second complete copy (identical bytes)
    with c.lock:
        # Pin node 2's outbound load above any cap so the fetch must
        # start from node 0 (deterministic victim).
        c.directory._outbound[2] = 1_000
    f = c.get_async(1, "x", timeout=30.0)
    deadline = time.time() + 10.0
    while time.time() < deadline:
        buf = c.stores[1].get("x")
        if buf is not None and buf.bytes_present > 2 * 32 * 1024:
            break
        time.sleep(0.001)
    with c.lock:
        c.directory._outbound[2] = 0  # free the survivor
        mark = c.stores[1].get("x").bytes_present
    c.fail_node(0)
    np.testing.assert_array_equal(f.result(timeout=30.0), x)
    # The survivor streamed only the tail, not the whole object again
    # (slack: windows that landed between the mark and the kill).
    resumed = c.stats["bytes_served"].get(2, 0)
    assert 0 < resumed <= x.nbytes - mark + 4 * 32 * 1024, (
        f"restarted from zero: survivor served {resumed} of {x.nbytes} "
        f"(watermark at kill ~{mark})"
    )


def test_sibling_fetch_dedupe_single_inbound_stream():
    """Two concurrent Gets of one object on one node share a single
    inbound stream instead of streaming the bytes twice."""
    c = LocalCluster(2, chunk_size=32 * 1024, pace=0.001)
    x = np.random.RandomState(3).rand(150_000).astype(np.float32)
    c.put(0, "x", x)
    futs = [c.get_async(1, "x", timeout=30.0) for _ in range(4)]
    for f in futs:
        np.testing.assert_array_equal(f.result(timeout=30.0), x)
    inbound = [t for t in c.transfers if t[1] == 1]
    assert len(inbound) == 1, inbound
    assert c.stats["bytes_served"].get(0, 0) == x.nbytes


def test_first_location_all_candidates_dead_raises_promptly():
    """Satellite regression: when every group candidate is a stale
    location at a dead node, _first_location must raise ObjectLost
    promptly instead of spinning until the deadline."""
    c = LocalCluster(4)
    x = np.random.RandomState(4).rand(50_000)
    c.put(1, "src", x)
    # Stale state: the node is dead but its directory entries survived
    # (a kill racing directory cleanup / a failover resurrecting a
    # replica's view).  Bypass fail_node to build exactly that state.
    c.dead.add(1)
    t0 = time.time()
    with pytest.raises(ObjectLost):
        c._first_location(["src"], deadline=time.time() + 30.0, fallback=None)
    assert time.time() - t0 < 2.0, "spun to the deadline hunting a coordinator"


def test_chunk_autotune_default_and_override():
    """LocalCluster chunk sizing rides planner.CollectiveConfig.chunks_for
    (imported directly, no fallback) unless explicitly overridden."""
    auto = LocalCluster(8)
    big, small = 4 << 20, 64 << 10
    cb, cs = auto.chunk_size_for(big), auto.chunk_size_for(small)
    assert cb % 64 == 0 and cs % 64 == 0
    assert cb > cs  # bigger objects stream in bigger chunks
    assert auto.chunk_size_for(big) * 1 < big  # genuinely chunked
    pinned = LocalCluster(8, chunk_size=8192)
    assert pinned.chunk_size_for(big) == 8192
    assert pinned.chunk_size_for(small) == 8192
    # Autotuned buffers still round-trip correctly.
    x = np.random.RandomState(5).rand(300_000)
    auto.put(0, "x", x)
    np.testing.assert_array_equal(auto.get(3, "x"), x)


def test_planner_pipelined_multicast_beats_store_forward_large():
    S = 256 << 20
    assert planner.t_pipelined_multicast(15, EC2_LINK, S, 4096) < (
        planner.t_binomial_store_forward(15, EC2_LINK, S)
    )
