"""The unified telemetry layer (`repro.obs`): recorder/span/point units,
metrics + exporters, the report CLI, device-resident solver counters, the
StageClocks sample rework, and the three cross-cutting guarantees of the
PR: (a) same-seed runs emit identical event streams modulo timing,
(b) instrumentation adds ZERO compiled shapes recorder on or off
(via the shared `compile_counter` fixture), and (c) the disabled-path
overhead of the instrumentation sites is < 2% of serve wall time.
"""
import math
import time
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

from repro import (AllocationRequest, Problem, RegionAllocator, SolverSpec,
                   Weights, make_system, solve, obs)
from repro.core.bcd import allocate, allocate_fleet, stack_systems
from repro.region.admission import StageClocks

W = Weights(0.5, 0.5, 1.0)


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Every test starts and ends on the default no-op recorder."""
    obs.set_recorder(None)
    yield
    obs.set_recorder(None)


def _mk_cells(sizes, seed=0):
    key = jax.random.PRNGKey(seed)
    return [(f"cell{i}-{n}", make_system(jax.random.fold_in(key, i),
                                         n_devices=n))
            for i, n in enumerate(sizes)]


def _serve(cells, spec, w=W, cells_per_batch=2):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        svc = RegionAllocator(w, cells_per_batch=cells_per_batch,
                              min_bucket=8, spec=spec)
        for cid, s in cells:
            svc.submit(AllocationRequest(cell_id=cid, sys=s))
        return svc.flush()


# ---------------------------------------------------------------------------
# recorder / spans / points
# ---------------------------------------------------------------------------

def test_span_nesting_and_ids():
    rec = obs.MemoryRecorder()
    obs.set_recorder(rec)
    with obs.span("outer", tag="a"):
        with obs.span("inner"):
            obs.point("evt", k=3)
    obs.set_recorder(None)

    assert [e["name"] for e in rec.events] == ["evt", "inner", "outer"]
    evt, inner, outer = rec.events
    assert outer["parent"] == -1 and outer["span"] == 0
    assert inner["parent"] == outer["span"] and inner["span"] == 1
    assert evt["span"] == inner["span"] and evt["type"] == "point"
    assert outer["tag"] == "a" and evt["k"] == 3
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0


def test_span_ids_reset_on_install():
    for _ in range(2):
        rec = obs.MemoryRecorder()
        obs.set_recorder(rec)
        with obs.span("s"):
            pass
        assert rec.events[0]["span"] == 0


def test_disabled_path_is_inert():
    assert not obs.enabled()
    s1 = obs.span("anything", big_attr=list(range(100)))
    s2 = obs.span("else")
    assert s1 is s2            # one cached null context manager
    with s1:
        assert obs.point("evt", x=1) is None


def test_strip_timing():
    ev = dict(type="point", name="x", span=0, parent=0,
              ts=123.0, dur_s=0.5, latency_s=0.1, iters=3, stage="plan")
    assert obs.strip_timing(ev) == dict(type="point", name="x", span=0,
                                        parent=0, iters=3, stage="plan")


def test_jsonl_recorder_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with obs.recording(obs.JsonlRecorder(path)):
        with obs.span("run", n=np.int64(2)):     # numpy scalars coerce
            obs.point("evt", v=np.float64(1.5))
    events = obs.read_jsonl(path)
    assert [e["name"] for e in events] == ["evt", "run"]
    assert events[0]["v"] == 1.5 and events[1]["n"] == 2


def test_recording_restores_previous():
    outer = obs.MemoryRecorder()
    obs.set_recorder(outer)
    with obs.recording(obs.MemoryRecorder()) as inner:
        obs.point("inner_evt")
    obs.point("outer_evt")
    obs.set_recorder(None)
    assert [e["name"] for e in inner.events] == ["inner_evt"]
    assert [e["name"] for e in outer.events] == ["outer_evt"]


# ---------------------------------------------------------------------------
# metrics + exporters
# ---------------------------------------------------------------------------

def test_counter_and_gauge():
    reg = obs.MetricsRegistry()
    c = reg.counter("requests", stage="plan")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("requests", stage="plan") is c     # get-or-create
    assert reg.counter("requests", stage="gather") is not c
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_histogram_percentiles_accuracy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-4.0, sigma=0.8, size=4000)   # ~1.8e-2 s
    h = obs.Histogram("lat")
    h.observe_many(vals)
    assert h.count == 4000
    for q in (50, 90, 99):
        exact = np.percentile(vals, q)
        got = h.percentile(q)
        # bucket growth is 7%: interpolated percentiles must sit inside it
        assert abs(got - exact) / exact < 0.07, (q, got, exact)
    assert h.percentile(0) == vals.min()
    assert h.percentile(100) == vals.max()
    assert math.isnan(obs.Histogram("empty").percentile(50))


def test_prometheus_text_and_jsonl_export():
    reg = obs.MetricsRegistry()
    reg.counter("req", stage="plan").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat")
    h.observe_many([0.001, 0.002, 0.004, 5.0])
    text = obs.prometheus_text(reg)
    assert 'req_total{stage="plan"} 3.0' in text
    assert "# TYPE req_total counter" in text
    assert "depth 2.0" in text
    assert 'le="+Inf"} 4' in text
    assert "lat_count 4" in text

    records = obs.metrics_jsonl(reg)
    kinds = {r["kind"] for r in records}
    assert kinds == {"counter", "gauge", "histogram"}
    hist = next(r for r in records if r["kind"] == "histogram")
    assert hist["count"] == 4 and hist["min"] == 0.001 and hist["max"] == 5.0
    assert "p99" in hist


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------

def test_report_cli_renders_tables(tmp_path, capsys):
    from repro.obs import report

    path = str(tmp_path / "events.jsonl")
    with obs.recording(obs.JsonlRecorder(path)):
        with obs.span("solve"):
            obs.point("stage", stage="plan", dur_s=0.002)
            obs.point("stage", stage="gather", dur_s=0.001)
            obs.point("request", cell_id="c0", bucket=8, warm=False,
                      iters=3, converged=True, batch_seq=0,
                      bcd_iters=3.0, sp1_evals=147.0, sp2_evals=122.0,
                      residual=1e-7, latency_s=0.015)
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "== spans ==" in out and "solve" in out
    assert "== pipeline stages ==" in out and "plan" in out
    assert "== request latency ==" in out and "end_to_end" in out
    assert "== per-request solver counters ==" in out
    assert "bcd_iters" in out and "sp2_evals" in out
    assert "p50_ms" in out and "p99_ms" in out


# ---------------------------------------------------------------------------
# device-resident solver counters
# ---------------------------------------------------------------------------

def test_single_solve_counters_match_history():
    sysp = make_system(jax.random.PRNGKey(1), n_devices=6)
    res = allocate(sysp, W, max_iters=8, keep_history=True)
    ctr = res.counters
    assert ctr is not None
    d = ctr.as_dict()
    assert set(d) == {"bcd_iters", "sp1_evals", "sp2_evals", "residual"}
    assert d["bcd_iters"] == res.iters
    assert d["sp2_evals"] == sum(row["sp2_iters"] for row in res.history)
    assert d["residual"] == pytest.approx(res.history[-1]["rel_step"])
    from repro.core.sp1 import dual_evals_per_iter
    from repro.core.accuracy import default_accuracy
    per = dual_evals_per_iter("sweep", default_accuracy())
    assert d["sp1_evals"] == res.iters * per


def test_fleet_counters_shape_and_slicing():
    key = jax.random.PRNGKey(2)
    batch = stack_systems([make_system(jax.random.fold_in(key, i),
                                       n_devices=6) for i in range(3)])
    res = allocate_fleet(batch, W, max_iters=8)
    assert res.counters is not None
    assert res.counters.data.shape == (3, 4)
    iters = np.asarray(res.counters.col("bcd_iters"))
    np.testing.assert_array_equal(iters, np.asarray(res.iters, float))
    assert np.all(np.asarray(res.counters.col("sp2_evals")) > 0)


def test_zero_iter_solve_counters():
    sysp = make_system(jax.random.PRNGKey(3), n_devices=6)
    res = allocate(sysp, W, max_iters=0)
    d = res.counters.as_dict()
    assert d["bcd_iters"] == 0 and d["sp1_evals"] == 0
    assert d["sp2_evals"] == 0 and math.isnan(d["residual"])


def test_rounds_ledger_sp2_evals_column():
    from repro.dynamics import RoundsConfig
    from repro.dynamics.config import ROUND_COLS

    assert ROUND_COLS[-1] == "sp2_evals"
    sysp = make_system(jax.random.PRNGKey(4), n_devices=6)
    cfg = RoundsConfig(rounds=3, bcd_iters=6)
    res = solve(Problem(system=sysp, weights=W, rounds=cfg,
                        key=jax.random.PRNGKey(5)))
    ev = np.asarray(res.ledger[:, ROUND_COLS.index("sp2_evals")])
    assert np.all(ev > 0)
    # warm-started re-allocation rounds must not cost more dual evals
    # than the cold round-0 solve (the warm-start attribution claim)
    assert np.all(ev[1:] <= ev[0])


# ---------------------------------------------------------------------------
# StageClocks: per-sample semantics + deprecated aggregate shims
# ---------------------------------------------------------------------------

def test_stage_clocks_samples_and_shims():
    clocks = StageClocks()
    clocks.record("plan", 0.002)
    clocks.record("plan", 0.004)
    assert clocks.samples("plan") == [0.002, 0.004]
    assert clocks.count("plan") == 2
    assert clocks.total("plan") == pytest.approx(0.006)
    # deprecated aggregate read
    assert clocks.plan_s == pytest.approx(0.006)
    # deprecated aggregate `+=` records the delta as one more sample
    clocks.plan_s += 0.003
    assert clocks.count("plan") == 3
    assert clocks.samples("plan")[-1] == pytest.approx(0.003)
    # historical as_dict key set is unchanged
    assert set(clocks.as_dict()) == {f"{s}_s" for s in StageClocks.STAGES}
    p = clocks.percentiles("plan")
    assert set(p) == {"p50", "p90", "p99"}
    assert 0.002 <= p["p50"] <= 0.004
    assert math.isnan(clocks.percentiles("gather")["p50"])


def test_stage_clocks_emit_obs_points():
    rec = obs.MemoryRecorder()
    obs.set_recorder(rec)
    clocks = StageClocks()
    clocks.record("dispatch", 0.001)
    obs.set_recorder(None)
    clocks.record("gather", 0.001)      # disabled again: no event
    stages = [e for e in rec.events if e["name"] == "stage"]
    assert len(stages) == 1
    assert stages[0]["stage"] == "dispatch"
    assert stages[0]["dur_s"] == pytest.approx(0.001)


# ---------------------------------------------------------------------------
# end-to-end: serve trace telemetry, determinism, jit-cache guard, overhead
# ---------------------------------------------------------------------------

_SPEC = SolverSpec(max_iters=4, tol=1e-4)


def _trace_events(cells, spec):
    rec = obs.MemoryRecorder()
    with obs.recording(rec):
        _serve(cells, spec)
    return rec.events


def test_serve_trace_emits_full_telemetry():
    cells = _mk_cells([5, 7, 8])
    events = _trace_events(cells, _SPEC)
    names = {e["name"] for e in events}
    assert {"solve", "plan", "dispatch", "materialize",
            "stage", "request"} <= names
    requests = [e for e in events if e["name"] == "request"]
    assert {e["cell_id"] for e in requests} == {c for c, _ in cells}
    for r in requests:
        for k in ("bucket", "warm", "iters", "converged", "batch_seq",
                  "bcd_iters", "sp1_evals", "sp2_evals", "residual",
                  "latency_s"):
            assert k in r, k
        assert r["bcd_iters"] == r["iters"]
        assert r["latency_s"] >= 0.0
    solves = [e for e in events if e["name"] == "solve"]
    assert all(e["topology"] in ("bcd_fleet", "bcd_region")
               for e in solves)


def test_same_seed_runs_emit_identical_streams():
    cells = _mk_cells([5, 7, 8, 9])
    ev1 = [obs.strip_timing(e) for e in _trace_events(cells, _SPEC)]
    ev2 = [obs.strip_timing(e) for e in _trace_events(cells, _SPEC)]
    assert ev1 == ev2
    assert len(ev1) > 0


def test_recorder_adds_no_compiled_shapes(compile_counter):
    cells = _mk_cells([5, 7, 8, 9], seed=7)
    # warm-up with the recorder OFF: all compilation happens here
    _serve(cells, _SPEC)
    _serve(cells, _SPEC)
    before = compile_counter.count
    _serve(cells, _SPEC)                       # recorder off
    with obs.recording(obs.MemoryRecorder()):  # recorder ON, same trace
        _serve(cells, _SPEC)
    assert compile_counter.count == before, (
        f"telemetry triggered {compile_counter.count - before} recompiles")


def test_noop_recorder_overhead_under_2_percent():
    """The disabled instrumentation sites must cost < 2% of serve wall
    time. Deterministically: measure the per-call cost of a disabled
    span()/point(), count how many telemetry events the same trace emits
    when enabled (an upper bound on disabled-path site hits), and compare
    the product against the measured serve wall time."""
    cells = _mk_cells([5, 7, 8, 9, 12, 16], seed=11)
    _serve(cells, _SPEC)           # compile + warm caches
    _serve(cells, _SPEC)

    t0 = time.perf_counter()
    _serve(cells, _SPEC)
    wall = time.perf_counter() - t0

    rec = obs.MemoryRecorder()
    with obs.recording(rec):
        _serve(cells, _SPEC)
    n_sites = len(rec.events)
    assert n_sites > 0

    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs.span("x"):
            pass
        obs.point("x")
    per_site = (time.perf_counter() - t0) / (2 * reps)

    overhead = n_sites * per_site
    assert overhead < 0.02 * wall, (
        f"no-op telemetry {overhead * 1e6:.1f}us over {n_sites} sites vs "
        f"{wall * 1e3:.1f}ms serve wall ({overhead / wall:.2%})")


# ---------------------------------------------------------------------------
# Histogram non-finite guard + background JsonlRecorder (PR 9 satellites)
# ---------------------------------------------------------------------------

def test_histogram_drops_non_finite():
    h = obs.Histogram("lat")
    h.observe(0.01)
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe(float("-inf"))
    h.observe(0.02)
    assert h.count == 2
    assert h.dropped == 3
    assert h.sum == pytest.approx(0.03)
    assert math.isfinite(h.percentile(50))
    # the exporters surface the drop count instead of hiding it
    reg = obs.MetricsRegistry()
    hh = reg.histogram("lat")
    hh.observe(1.0)
    hh.observe(float("nan"))
    text = obs.prometheus_text(reg)
    assert "lat_dropped_total 1" in text
    rec = next(r for r in obs.metrics_jsonl(reg) if r["kind"] == "histogram")
    assert rec["dropped"] == 1


def test_histogram_observe_many_mixed_finiteness():
    h = obs.Histogram("lat")
    h.observe_many([0.001, float("nan"), 0.002, float("inf")])
    assert h.count == 2 and h.dropped == 2


def test_jsonl_recorder_background_flush(tmp_path):
    """Events written through the bounded queue land on disk, in emit
    order, once the recorder closes (recording() closes it)."""
    path = str(tmp_path / "bg.jsonl")
    with obs.recording(obs.JsonlRecorder(path)):
        for i in range(500):
            obs.point("evt", i=i)
    events = obs.read_jsonl(path)
    assert [e["i"] for e in events] == list(range(500))


def test_jsonl_recorder_drops_when_queue_full(tmp_path):
    """A stalled writer (deterministically held by the test gate) makes
    emits drop instead of blocking; the drops are counted locally and in
    the global obs_events_dropped counter; close() still flushes what
    queued."""
    path = str(tmp_path / "drop.jsonl")
    rec = obs.JsonlRecorder(path, queue_size=4)
    base = obs.counter("obs_events_dropped").value
    rec._drain_gate.clear()              # stall the writer
    # let the writer park on the gate holding one dequeued event
    rec.emit({"i": -1})
    deadline = time.perf_counter() + 5.0
    while rec._queue.qsize() and time.perf_counter() < deadline:
        time.sleep(0.001)
    for i in range(4):                   # refill the queue exactly
        rec.emit({"i": i})
    rec.emit({"i": 99})                  # queue full -> dropped
    rec.emit({"i": 100})
    assert rec.dropped_events == 2
    assert obs.counter("obs_events_dropped").value == base + 2
    rec._drain_gate.set()
    rec.close()
    got = [e["i"] for e in obs.read_jsonl(path)]
    assert got == [-1, 0, 1, 2, 3]
    rec.emit({"i": 101})                 # emit-after-close counts as drop
    assert rec.dropped_events == 3


# ---------------------------------------------------------------------------
# spans on the profiler's clock, solve's stages, the solver's named scopes
# ---------------------------------------------------------------------------

def _trace_names(logdir) -> set:
    """Every event name of the newest `*.xplane.pb` under `logdir`."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return {e.name for p in ProfileData.from_file(path).planes
            for ln in p.lines for e in ln.events}


def test_span_writes_a_trace_event_without_a_recorder(tmp_path):
    assert not obs.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.span("x") is not obs.span("y")
        with obs.span("x"):
            with obs.span("x.inner", ignored=1):
                pass
    finally:
        jax.profiler.stop_trace()
    # the session closed: back to the one cached null object
    assert obs.span("x") is obs.span("y")
    names = _trace_names(tmp_path)
    assert {"repro.x", "repro.x.inner"} <= names


def test_recorded_span_also_writes_a_trace_event(tmp_path):
    rec = obs.MemoryRecorder()
    obs.set_recorder(rec)
    with obs.span("before"):       # no session yet: the recorder only
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("during", tag="a"):
            pass
    finally:
        jax.profiler.stop_trace()
    obs.set_recorder(None)
    assert [e["name"] for e in rec.events] == ["before", "during"]
    names = _trace_names(tmp_path)
    assert "repro.during" in names and "repro.before" not in names


def test_spans_enter_no_named_scope():
    """A span names host time only: inside it JAX's name stack, which a
    program traced there would carry, is what it was outside."""
    from jax._src import source_info_util

    outside = str(source_info_util.current_name_stack())
    obs.set_recorder(obs.MemoryRecorder())
    with obs.span("hostwork"):
        assert str(source_info_util.current_name_stack()) == outside


def _problems():
    from repro.region import region_mesh

    key = jax.random.PRNGKey(5)
    cell = make_system(key, n_devices=6)
    fleet = stack_systems([make_system(jax.random.fold_in(key, i),
                                       n_devices=6) for i in range(2)])
    return {
        "bcd": Problem(system=cell, weights=W),
        "bcd_fleet": Problem(system=fleet, weights=W),
        "bcd_region": Problem(system=fleet, weights=W,
                              mesh=region_mesh(1)),
        "fixed_fleet": Problem(system=fleet, weights=W, deadline=40.0),
    }


@pytest.mark.parametrize("topology", ["bcd", "bcd_fleet", "bcd_region",
                                      "fixed_fleet"])
def test_solve_stages_nest_under_solve(topology):
    problem = _problems()[topology]
    spec = SolverSpec(max_iters=3, tol=1e-4)
    runs = []
    for _ in range(2):
        rec = obs.MemoryRecorder()
        with obs.recording(rec):
            solve(problem, spec)
        runs.append([obs.strip_timing(e) for e in rec.events
                     if e["type"] == "span"])
    assert runs[0] == runs[1]          # deterministic ids
    assert [(e["name"], e["span"], e["parent"]) for e in runs[0]] == [
        ("solve.prepare", 1, 0), ("solve.launch", 2, 0),
        ("solve.result", 3, 0), ("solve", 0, -1)]
    assert runs[0][-1]["topology"] == topology


def test_solver_scopes_name_the_device_program():
    """The compiled fleet solve's ops carry `bcd`, `sp1` and `sp2` in
    their name stacks (the `tf_op` a device trace shows)."""
    import re

    from repro.api.problem import weights_leaf
    from repro.core.accuracy import default_accuracy
    from repro.core.bcd import _fleet_solve_impl
    from repro.kernels.ops import kernel_mode

    fleet = stack_systems([make_system(jax.random.PRNGKey(i), n_devices=6)
                           for i in range(2)])
    spec = SolverSpec(max_iters=2, tol=1e-4)
    dtype = jax.numpy.asarray(fleet.gain).dtype
    warr = weights_leaf([W, W], dtype, cells=2)
    hlo = _fleet_solve_impl.lower(
        fleet, warr, None, np.asarray(spec.tol, dtype), default_accuracy(),
        spec.max_iters, spec.sp1_method, spec.sp2_method, spec.sp2_iters,
        kernel_mode(), False).compile().as_text()
    stacks = [set(n.split("/")) for n in re.findall(r'op_name="([^"]*)"',
                                                    hlo)]
    assert any({"bcd", "sp1"} <= s for s in stacks)
    assert any({"bcd", "sp2"} <= s for s in stacks)
    assert not any({"sp1", "sp2"} <= s for s in stacks)


def test_traced_fleet_solves_add_no_compiled_shapes(compile_counter,
                                                    tmp_path):
    fleet = stack_systems([make_system(jax.random.PRNGKey(9 + i),
                                       n_devices=7) for i in range(2)])
    problem = Problem(system=fleet, weights=W)
    spec = SolverSpec(max_iters=3, tol=1e-4)
    for _ in range(2):                 # compile with both sinks off
        jax.block_until_ready(solve(problem, spec).objective)
    before = compile_counter.count
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.recording(obs.MemoryRecorder()):
            jax.block_until_ready(solve(problem, spec).objective)
        jax.block_until_ready(solve(problem, spec).objective)
    finally:
        jax.profiler.stop_trace()
    assert compile_counter.count == before, (
        f"tracing triggered {compile_counter.count - before} recompiles")
    assert {"repro.solve", "repro.solve.prepare", "repro.solve.launch",
            "repro.solve.result"} <= _trace_names(tmp_path)
