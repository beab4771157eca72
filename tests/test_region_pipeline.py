"""Pipelined serving stack (admission -> planning -> dispatch ->
completion): facade bit-parity with the serial depth-1 path, inactive
pad lanes leaving real lanes bit-identical, out-of-order future
completion, batch-closing policies under a logical clock, warm-start
coherence for in-flight cells, and LRU / stage-clock accounting."""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro import Problem, SolverSpec, solve
from repro.core import Weights, make_system
from repro.core.bcd import initial_allocation, stack_systems
from repro.region import (AllocationRequest, CloseOnFull, DeadlineSlack,
                          MaxWait, RegionAllocator, RegionPipeline,
                          WarmStartCache, inactive_system, pad_system)
from repro.region.planning import BatchPlanner, _full_allocation

W = Weights(0.5, 0.5, 1.0)
SPEC = SolverSpec(max_iters=8, tol=1e-5)


def _req(cell_id, n, seed=None, drift=0.0, **kw):
    sysp = make_system(jax.random.PRNGKey(seed if seed is not None
                                          else 100 + hash(cell_id) % 1000),
                       n_devices=n)
    if drift:
        sysp = sysp.replace(gain=sysp.gain * (1.0 + drift))
    return AllocationRequest(cell_id=cell_id, sys=sysp, **kw)


def _pipeline(**kw):
    kw.setdefault("cells_per_batch", 2)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("spec", SPEC)
    return RegionPipeline(W, **kw)


def _resp_equal(a, b):
    if (a.cell_id, a.objective, a.iters, a.converged, a.warm,
            a.bucket) != (b.cell_id, b.objective, b.iters, b.converged,
                          b.warm, b.bucket):
        return False
    eq = jax.tree_util.tree_map(
        lambda x, y: bool(jnp.all(jnp.asarray(x) == jnp.asarray(y))),
        a.allocation, b.allocation)
    return all(jax.tree_util.tree_leaves(eq))


# ---------------------------------------------------------------------------
# bit-parity: pipelining changes timing, never results
# ---------------------------------------------------------------------------

def test_pipeline_depth_is_bit_invisible():
    """The same trace through depth 1 (the old serial solve-then-gather
    loop) and depth 3 produces bit-identical responses and identical
    cache/shape accounting."""
    sizes = [5, 9, 6, 14, 7, 12, 11, 6, 30]
    traces = []
    for depth in (1, 3):
        svc = RegionAllocator(W, cells_per_batch=2, min_bucket=8, spec=SPEC,
                              pipeline_depth=depth)
        out1 = svc.solve([_req(i, n) for i, n in enumerate(sizes)])
        out2 = svc.solve([_req(i, n, drift=0.01)
                          for i, n in enumerate(sizes)])
        stats = dict(svc.stats)
        stats["shapes"] = set(stats["shapes"])
        traces.append((out1, out2, stats))
    (a1, a2, sa), (b1, b2, sb) = traces
    assert sa == sb
    for out_a, out_b in ((a1, b1), (a2, b2)):
        assert set(out_a) == set(out_b)
        for cid in out_a:
            assert _resp_equal(out_a[cid], out_b[cid]), cid
    assert all(r.warm for r in a2.values())


def test_inactive_pad_lanes_keep_real_lanes_bit_identical():
    """A short chunk padded with all-inactive filler cells solves its real
    lanes bit-identically to the old replicate-cell-0 padding (vmapped
    per-cell programs are independent), while the filler lane itself
    converges after one masked iteration."""
    C, bucket = 4, 8
    reqs = [_req(i, 6) for i in range(3)]
    padded = [pad_system(r.sys, bucket) for r in reqs]
    inits = [_full_allocation(initial_allocation(p)) for p in padded]

    def batch(filler_sys, filler_init):
        sys_b = stack_systems(padded + [filler_sys])
        init_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                        *(inits + [filler_init]))
        return solve(Problem(system=sys_b, weights=[W] * C, init=init_b),
                     SPEC)

    new = batch(inactive_system(padded[0]),
                _full_allocation(initial_allocation(
                    inactive_system(padded[0]))))
    old = batch(padded[0], inits[0])
    for leaf_new, leaf_old in zip(
            jax.tree_util.tree_leaves(new.allocation),
            jax.tree_util.tree_leaves(old.allocation)):
        np.testing.assert_array_equal(np.asarray(leaf_new)[:3],
                                      np.asarray(leaf_old)[:3])
    np.testing.assert_array_equal(np.asarray(new.objective[:3]),
                                  np.asarray(old.objective[:3]))
    np.testing.assert_array_equal(np.asarray(new.iters[:3]),
                                  np.asarray(old.iters[:3]))
    # the all-inactive lane sits at the masked fixed point: one iteration
    assert int(new.iters[3]) == 1 and bool(new.converged[3])


# ---------------------------------------------------------------------------
# futures: out-of-order completion
# ---------------------------------------------------------------------------

def test_out_of_order_result_materializes_only_its_batch():
    pipe = _pipeline(max_in_flight=4)
    futs = [pipe.submit(_req(i, 6)) for i in range(4)]   # 2 batches of 2
    batches = pipe.pump(force=True)
    assert len(batches) == 2 and pipe.in_flight == 2
    assert all(f.dispatched and not f.done() for f in futs)

    late = futs[3].result()            # batch 2 first
    assert futs[3].done() and futs[2].done()
    assert not futs[0].done() and not futs[1].done()
    assert batches[1].materialized and not batches[0].materialized
    assert pipe.in_flight == 1
    assert late.cell_id == 3

    early = futs[0].result()           # batch 1 afterwards — still fine
    assert early.cell_id == 0 and pipe.in_flight == 0
    assert all(f.done() for f in futs)


def test_result_on_queued_request_forces_dispatch():
    pipe = _pipeline()
    fut = pipe.submit(_req("solo", 6))
    assert not fut.dispatched and pipe.pending == 1
    res = fut.result()
    assert res.cell_id == "solo" and fut.done()
    assert pipe.pending == 0 and pipe.in_flight == 0


def test_depth_bound_evicts_oldest():
    pipe = _pipeline(max_in_flight=1)
    futs = [pipe.submit(_req(i, 6)) for i in range(4)]
    pipe.pump(force=True)
    assert pipe.in_flight == 1          # batch 1 was force-materialized
    assert futs[0].done() and futs[1].done()
    assert not futs[3].done()


# ---------------------------------------------------------------------------
# admission policies under a logical clock
# ---------------------------------------------------------------------------

def test_close_on_full_waits_for_full_batches():
    pipe = _pipeline(policy=CloseOnFull())
    pipe.submit(_req(0, 6), now=0.0)
    assert pipe.poll(now=1e9) == []     # partial batch never closes
    pipe.submit(_req(1, 6), now=2.0)
    (batch,) = pipe.poll(now=3.0)
    assert batch.plan.n_real == 2 and pipe.pending == 0


def test_max_wait_closes_partial_batches():
    pipe = _pipeline(policy=MaxWait(10.0))
    pipe.submit(_req(0, 6), now=0.0)
    assert pipe.poll(now=9.0) == []
    (batch,) = pipe.poll(now=10.0)      # oldest waited exactly max_wait
    assert batch.plan.n_real == 1
    # the wait was charged to the admission clock in logical units
    assert pipe.clocks.queue_wait_s == pytest.approx(10.0)


def test_deadline_slack_closes_for_tight_requests():
    pipe = _pipeline(cells_per_batch=3, policy=DeadlineSlack(slack=5.0))
    pipe.submit(_req(0, 6), now=0.0)                       # no deadline
    pipe.submit(_req(1, 6, deadline=20.0), now=0.0)
    assert pipe.poll(now=10.0) == []                       # 10 > slack
    (batch,) = pipe.poll(now=15.0)                         # 5 <= slack
    assert batch.plan.n_real == 2                          # rides along
    with pytest.raises(ValueError):
        MaxWait(-1.0)


def test_priority_orders_within_batch():
    pipe = _pipeline(cells_per_batch=3)
    pipe.submit(_req("lo", 6, priority=0), now=0.0)
    pipe.submit(_req("hi", 6, priority=5), now=0.0)
    pipe.submit(_req("mid", 6, priority=1), now=0.0)
    (batch,) = pipe.pump(now=0.0, force=True)
    assert [r.cell_id for r in batch.plan.requests] == ["hi", "mid", "lo"]


# ---------------------------------------------------------------------------
# warm-start coherence + LRU accounting
# ---------------------------------------------------------------------------

def test_in_flight_cell_stalls_replan_until_cache_written():
    """A re-request of a cell whose solve is still in flight must wait for
    that solution to land in the cache — the second batch plans warm, same
    as the synchronous path."""
    pipe = _pipeline(cells_per_batch=1, max_in_flight=2)
    pipe.submit(_req("x", 6, seed=1))
    (first,) = pipe.pump(force=True)
    assert pipe.in_flight == 1 and not first.materialized
    pipe.submit(_req("x", 6, seed=1, drift=0.01))
    (second,) = pipe.pump(force=True)
    assert first.materialized            # drained before planning "x" again
    assert second.plan.warm == [True]
    out = pipe.drain()
    assert [r.warm for r in out] == [False, True]
    assert out[1].iters <= 3


def test_duplicate_cell_id_in_one_solve_keeps_last_response():
    svc = RegionAllocator(W, cells_per_batch=1, min_bucket=8, spec=SPEC)
    res = svc.solve([_req("dup", 6, seed=3),
                     _req("dup", 6, seed=3, drift=0.02)])
    assert set(res) == {"dup"}
    assert res["dup"].warm               # dict keeps the later chunk's row
    assert svc.stats["requests"] == 2 and svc.stats["batches"] == 2


def test_warm_cache_resize_purge_frees_capacity():
    cache = WarmStartCache(2)
    alloc = initial_allocation(make_system(jax.random.PRNGKey(0),
                                           n_devices=4))
    cache.store("a", 4, alloc)
    cache.store("b", 4, alloc)
    assert cache.lookup("b", 4) is alloc and cache.hits == 1
    # pool resize: the stale entry is purged immediately, not just missed
    assert cache.lookup("a", 8) is None
    assert cache.resize_purges == 1 and cache.misses == 1
    assert "a" not in cache and len(cache) == 1
    # the freed slot absorbs a new cell without evicting "b"
    cache.store("c", 4, alloc)
    assert cache.evictions == 0 and "b" in cache
    cache.store("d", 4, alloc)           # now over capacity: "b" is LRU
    assert cache.evictions == 1 and "b" not in cache
    with pytest.raises(ValueError):
        WarmStartCache(0)


def test_interleaved_buckets_warm_hit_accounting():
    """Re-requests interleaved across two buckets all warm-hit; hit/miss
    counters add up across the pipeline and the cache agree."""
    svc = RegionAllocator(W, cells_per_batch=2, min_bucket=8, spec=SPEC)
    sizes = {0: 6, 1: 12, 2: 7, 3: 14}
    svc.solve([_req(i, n) for i, n in sizes.items()])
    res = svc.solve([_req(i, n, drift=0.01) for i, n in sizes.items()])
    assert all(r.warm for r in res.values())
    assert svc.stats["cache_hits"] == 4
    assert svc.stats["cache_misses"] == 4
    assert svc.pipeline.cache.hits == 4
    assert svc.pipeline.cache.misses == 4
    assert len(svc.compiled_shapes) == 2   # (2, 8) and (2, 16)


# ---------------------------------------------------------------------------
# stage clocks
# ---------------------------------------------------------------------------

def test_stage_clocks_cover_all_four_layers():
    pipe = _pipeline()
    for i in range(4):
        pipe.submit(_req(i, 6), now=float(i))
    out = pipe.drain(now=10.0)
    assert len(out) == 4
    clocks = pipe.clocks.as_dict()
    assert set(clocks) == {"queue_wait_s", "plan_s", "dispatch_s",
                           "gather_s"}
    # logical admission clock: waits are 10-0, 10-1, 10-2, 10-3
    assert clocks["queue_wait_s"] == pytest.approx(34.0)
    for key in ("plan_s", "dispatch_s", "gather_s"):
        assert clocks[key] > 0.0, key


# ---------------------------------------------------------------------------
# handover invalidation (mobility churn hook)
# ---------------------------------------------------------------------------

def test_handover_invalidate_purges_and_counts():
    """invalidate() drops exactly the named cell's warm entry, counts it in
    handover_purges (pipeline stats AND cache counter), and forces the next
    request for that cell to re-solve cold."""
    svc = RegionAllocator(W, cells_per_batch=2, min_bucket=8, spec=SPEC)
    svc.solve([_req("a", 6, seed=1), _req("b", 6, seed=2)])
    assert svc.stats["handover_purges"] == 0

    assert svc.invalidate("a") is True
    assert svc.invalidate("a") is False     # already gone: not double-counted
    assert svc.invalidate("nope") is False  # unknown cell: a no-op
    assert svc.stats["handover_purges"] == 1
    assert svc.pipeline.cache.handover_purges == 1

    res = svc.solve([_req("a", 6, seed=1, drift=0.01),
                     _req("b", 6, seed=2, drift=0.01)])
    assert not res["a"].warm                # purged -> cold re-solve
    assert res["b"].warm                    # untouched cell stays warm


def test_handover_invalidate_materializes_in_flight_batch():
    """An invalidation racing an in-flight async batch must not let the
    stale store resurrect: the pending batch is materialized first, then
    purged, so the next solve is cold."""
    pipe = _pipeline(cells_per_batch=1, max_in_flight=2)
    pipe.submit(_req("x", 6, seed=9))
    pipe.pump(force=True)
    assert pipe._in_flight                  # batch launched, not gathered
    assert pipe.invalidate("x") is True
    assert not pipe._in_flight              # forced materialization
    assert pipe.stats["handover_purges"] == 1
    out = pipe.drain()
    assert len(out) == 1 and out[0].cell_id == "x" and out[0].converged
    pipe.submit(_req("x", 6, seed=9, drift=0.01))
    resp = pipe.drain()[0]
    assert not resp.warm
