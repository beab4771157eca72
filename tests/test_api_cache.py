"""Jit-cache discipline of the unified API: compiles are keyed by
(bucket x topology x SolverSpec) — weights are traced operands and NEVER
trigger a recompile.

Compilations are counted through `jax.monitoring`'s backend-compile
duration events (every XLA backend compile fires one), measured as deltas
around a warmed mixed-weights / mixed-bucket request trace. The listener
(`CompileCounter`) lives in `tests/conftest.py` as the shared
`compile_counter` fixture — `test_obs` reuses it to prove the telemetry
plumbing adds no compiled shapes.
"""
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro import (AllocationRequest, Problem, RegionAllocator, SolverSpec,
                   Weights, make_fleet, make_system, obs, solve)


def _mk_cells(sizes, seed=0):
    key = jax.random.PRNGKey(seed)
    return {f"cell{i}-{n}": make_system(jax.random.fold_in(key, i),
                                        n_devices=n)
            for i, n in enumerate(sizes)}


def _drift(sys, scale):
    """Host-side gain drift: no eager jnp ops, so it cannot compile."""
    return sys.replace(gain=np.asarray(sys.gain) * scale)


def _submit_all(svc, cells, weights_of):
    for i, (cid, s) in enumerate(sorted(cells.items())):
        svc.submit(AllocationRequest(cell_id=cid, sys=s, w=weights_of(i)))
    return svc.flush()


def test_mixed_weights_trace_compiles_only_per_bucket(compile_counter):
    """The acceptance trace: mixed device counts (2 buckets) x mixed
    per-request weights compile once per (bucket, spec) and ZERO extra
    shapes for any weight change — the PR 4 fragmentation caveat closed."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        spec = SolverSpec(max_iters=4, tol=1e-4)
        svc = RegionAllocator(Weights(0.5, 0.5, 1.0), cells_per_batch=2,
                              min_bucket=8, spec=spec)
        # sizes straddling two power-of-two buckets: 8 and 16
        cells = _mk_cells([5, 7, 8, 9, 12, 16])
        w0 = Weights(0.5, 0.5, 1.0)

        # warm-up: cold pass, then a warm re-request pass (exercises the
        # warm-init padding host ops) — all compilation happens here
        _submit_all(svc, cells, lambda i: w0)
        cells = {cid: _drift(s, 1.01) for cid, s in cells.items()}
        _submit_all(svc, cells, lambda i: w0)
        assert svc.compiled_shapes == {(2, 8), (2, 16)}   # == #buckets

        # measurement: three more passes, every request with NEW weights
        before = compile_counter.count
        for k in range(3):
            cells = {cid: _drift(s, 1.0 + 0.01 * (k + 1))
                     for cid, s in cells.items()}
            out = _submit_all(
                svc, cells,
                lambda i, k=k: Weights(0.1 + 0.1 * i + 0.01 * k,
                                       0.9 - 0.1 * i, 1.0 + i + k))
            assert all(r.warm for r in out.values())
        assert compile_counter.count == before, (
            f"{compile_counter.count - before} recompiles triggered by "
            f"weight-only changes")
        assert svc.compiled_shapes == {(2, 8), (2, 16)}

        # a NEW spec is a new cache key: the same trace recompiles...
        svc2 = RegionAllocator(w0, cells_per_batch=2, min_bucket=8,
                               spec=SolverSpec(max_iters=5, tol=1e-4))
        before = compile_counter.count
        _submit_all(svc2, cells, lambda i: w0)
        assert compile_counter.count > before
        # ...and an equal spec in a fresh allocator hits the global cache
        svc3 = RegionAllocator(w0, cells_per_batch=2, min_bucket=8,
                               spec=SolverSpec(max_iters=5, tol=1e-4))
        cells = {cid: _drift(s, 1.005) for cid, s in cells.items()}
        before = compile_counter.count
        _submit_all(svc3, cells, lambda i: w0)
        assert compile_counter.count == before


def test_single_cell_weight_changes_do_not_recompile(compile_counter):
    """Same discipline on the single-cell topology through bare solve()."""
    sysp = make_system(jax.random.PRNGKey(3), n_devices=6)
    spec = SolverSpec(max_iters=3, tol=1e-4)
    solve(Problem(system=sysp, weights=Weights(0.5, 0.5, 1.0)), spec)
    solve(Problem(system=sysp, weights=Weights(0.4, 0.6, 2.0)), spec)  # warm
    before = compile_counter.count
    for i in range(4):
        solve(Problem(system=sysp,
                      weights=Weights(0.1 + 0.2 * i, 0.9 - 0.2 * i,
                                      float(i))), spec)
    assert compile_counter.count == before


def test_kernel_mode_is_part_of_the_jit_key(compile_counter, monkeypatch):
    """The SP1 kernel's mode is resolved outside jit and reaches the solve's
    jit key: asking for interpret mode after a ref-mode solve builds a new
    program instead of reusing the cached ref one, with the same answer."""
    from repro.kernels.ops import kernel_mode

    problem = Problem(system=make_system(jax.random.PRNGKey(3), n_devices=12),
                      weights=Weights(0.5, 0.5, 1.0))
    spec = SolverSpec(max_iters=6, tol=1e-8)
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    assert kernel_mode() == "ref"            # the CPU default
    ref = solve(problem, spec)
    before = compile_counter.count
    solve(problem, spec)
    assert compile_counter.count == before
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert kernel_mode() == "interpret"
    interp = solve(problem, spec)
    assert compile_counter.count > before
    np.testing.assert_allclose(interp.objective, ref.objective, rtol=1e-10)


def _fleet_traces() -> float:
    return obs.counter("solve_fleet_traces").value


def test_fleet_solve_program_is_cached(compile_counter):
    """A fleet re-plan runs one cached jitted program: once a shape is
    warm, new weights and drifted gains neither compile nor re-trace it
    (`solve_fleet_traces` stays flat), and a new cell or device count
    traces it exactly once more."""
    spec = SolverSpec(max_iters=3, tol=1e-4)
    fleet = make_fleet(jax.random.PRNGKey(11), n_cells=3, n_devices=23)
    fleet = fleet.replace(gain=np.asarray(fleet.gain))
    problem = lambda sysp, k: Problem(
        system=sysp, weights=[Weights(0.2 + 0.1 * c + 0.01 * k,
                                      0.8 - 0.1 * c, 1.0 + c + k)
                              for c in range(3)])
    for k in range(2):              # warm: the program and the host path
        jax.block_until_ready(solve(problem(fleet, k), spec).objective)
    before, traces = compile_counter.count, _fleet_traces()
    for k in range(3):
        fleet = _drift(fleet, 1.0 + 0.01 * (k + 1))
        res = solve(problem(fleet, 2 + k), spec)
        jax.block_until_ready(res.objective)
        assert bool(np.all(np.isfinite(np.asarray(res.objective))))
    assert compile_counter.count == before
    assert _fleet_traces() == traces

    # a new cell count, then a new device count: one trace each
    for C, N in ((5, 23), (3, 29)):
        other = make_fleet(jax.random.PRNGKey(12), n_cells=C, n_devices=N)
        traces = _fleet_traces()
        for _ in range(2):
            solve(Problem(system=other, weights=Weights(0.5, 0.5, 1.0)),
                  spec)
        assert _fleet_traces() == traces + 1, (C, N)
