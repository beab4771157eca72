"""Unified-API acceptance: one `solve(problem, spec)` reproduces every
legacy entry point bit-identically, per topology, and per-cell traced
weights match per-cell single solves exactly.

Also covers the SolverSpec construction-time validation (tol vs the
64-ulp rel-step floor) and the `allocate_fixed_deadline` parity satellite
(max_iters=0 returns NaN, spec options are honored).
"""
import dataclasses
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro import (Problem, SolverSpec, Weights, make_fleet, make_system,
                   rel_step_floor, solve)
from repro.api.problem import weights_leaf
from repro.api.solve import _reset_deprecation_registry
from repro.core.accuracy import default_accuracy
from repro.core.bcd import (_FIXED_COLS, _fleet_cell_fn, _fleet_fixed_cell_fn,
                            _fleet_result, initial_allocation, stack_systems)
from repro.kernels.ops import kernel_mode
from repro.core import allocate, allocate_fixed_deadline, allocate_fleet
from repro.dynamics import RoundsConfig, run_rounds_fleet
from repro.region import allocate_region, region_mesh
from repro.region.batch import pad_system

W = Weights(0.5, 0.5, 1.0)


def _shim(fn, *args, **kw):
    """Call a legacy shim with its DeprecationWarning silenced."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*args, **kw)


def _tree_equal(a, b) -> bool:
    eq = jax.tree_util.tree_map(
        lambda x, y: bool(jnp.all(jnp.asarray(x) == jnp.asarray(y))), a, b)
    return all(jax.tree_util.tree_leaves(eq))


# ---------------------------------------------------------------------------
# per-topology bit parity
# ---------------------------------------------------------------------------

def test_solve_matches_allocate_bit_identical():
    sysp = make_system(jax.random.PRNGKey(0), n_devices=10)
    old = _shim(allocate, sysp, W, max_iters=6, tol=1e-5)
    new = solve(Problem(system=sysp, weights=W),
                SolverSpec(max_iters=6, tol=1e-5))
    assert _tree_equal(old.allocation, new.allocation)
    assert old.objective == new.objective
    assert old.iters == new.iters and old.converged == new.converged
    assert old.history == new.history


def test_solve_matches_allocate_fleet_bit_identical():
    fleet = make_fleet(jax.random.PRNGKey(1), n_cells=4, n_devices=12)
    old = _shim(allocate_fleet, fleet, W, max_iters=6)
    new = solve(Problem(system=fleet, weights=W), SolverSpec(max_iters=6))
    assert _tree_equal(old.allocation, new.allocation)
    assert bool(jnp.all(old.objective == new.objective))
    assert bool(jnp.all(old.iters == new.iters))
    assert np.array_equal(np.asarray(old.history), np.asarray(new.history),
                          equal_nan=True)   # rows past iters are NaN-padded


def test_solve_matches_allocate_region_bit_identical():
    fleet = make_fleet(jax.random.PRNGKey(2), n_cells=3, n_devices=12)
    mesh = region_mesh()
    old = _shim(allocate_region, fleet, W, mesh=mesh, max_iters=6)
    new = solve(Problem(system=fleet, weights=W, mesh=mesh),
                SolverSpec(max_iters=6))
    assert _tree_equal(old.allocation, new.allocation)
    assert bool(jnp.all(old.fleet.objective == new.fleet.objective))
    assert old.stats["cells"] == new.stats["cells"]


def test_solve_matches_run_rounds_fleet_bit_identical():
    fleet = make_fleet(jax.random.PRNGKey(3), n_cells=3, n_devices=10)
    base = _shim(allocate_fleet, fleet, W, max_iters=6)
    cfg = RoundsConfig(rounds=3, channel_mode="markov", bcd_iters=2,
                       participation="stale", dropout_prob=0.05)
    key = jax.random.PRNGKey(7)
    old = _shim(run_rounds_fleet, key, fleet, W, cfg, init=base.allocation)
    new = solve(Problem(system=fleet, weights=W, rounds=cfg, key=key,
                        init=base.allocation))
    assert bool(jnp.all(old.ledger == new.ledger))
    assert bool(jnp.all(old.staleness == new.staleness))
    assert _tree_equal(old.allocation, new.allocation)


def test_solve_matches_fixed_deadline_bit_identical():
    sysp = make_system(jax.random.PRNGKey(4), n_devices=8)
    w = Weights(0.99, 0.01, 1.0)
    old = _shim(allocate_fixed_deadline, sysp, w, 120.0, max_iters=6)
    new = solve(Problem(system=sysp, weights=w, deadline=120.0),
                SolverSpec(max_iters=6))
    assert _tree_equal(old.allocation, new.allocation)
    assert old.objective == new.objective
    assert old.history == new.history


def test_fixed_deadline_fleet_matches_per_cell_single_solves():
    """A (C, N) stack with `deadline` vmaps the fixed-deadline BCD: every
    cell must match its own single-cell solve bit-for-bit, including with
    per-cell (C,) deadline budgets."""
    C = 3
    fleet = make_fleet(jax.random.PRNGKey(5), n_cells=C, n_devices=8)
    w = Weights(0.99, 0.01, 1.0)
    deadlines = jnp.asarray([90.0, 120.0, 150.0])
    spec = SolverSpec(max_iters=6)
    res = solve(Problem(system=fleet, weights=w, deadline=deadlines), spec)
    assert res.objective.shape == (C,)
    assert res.columns[0] == "energy"
    for c in range(C):
        cell = jax.tree_util.tree_map(lambda x: x[c], fleet)
        single = solve(Problem(system=cell, weights=w,
                               deadline=float(deadlines[c])), spec)
        got = jax.tree_util.tree_map(lambda x: x[c], res.allocation)
        assert _tree_equal(got, single.allocation), c
        assert bool(res.objective[c] == single.objective), c
        assert int(res.iters[c]) == single.iters, c
    # a scalar deadline broadcasts to every cell
    flat = solve(Problem(system=fleet, weights=w, deadline=120.0), spec)
    one = solve(Problem(
        system=jax.tree_util.tree_map(lambda x: x[1], fleet),
        weights=w, deadline=120.0), spec)
    assert bool(flat.objective[1] == one.objective)


def _eager_fleet(problem: Problem, spec: SolverSpec):
    """The fleet solve as it ran before the cached fleet programs: a fresh
    per-cell closure vmapped eagerly on every call, the cold start and the
    objective selection in eager ops (`_fleet_result`)."""
    sysp, init = problem.system, problem.init
    acc = default_accuracy()
    dtype = jnp.asarray(sysp.gain).dtype
    C = int(jnp.asarray(sysp.gain).shape[0])
    warr = weights_leaf(problem.weights, dtype, cells=C)
    if problem.deadline is None:
        fn = _fleet_cell_fn(acc, spec.max_iters, spec.tol, spec.sp1_method,
                            spec.sp2_method, spec.sp2_iters, kernel_mode(),
                            with_init=init is not None)
        out = jax.vmap(fn)(sysp, warr) if init is None \
            else jax.vmap(fn)(sysp, warr, init)
        return _fleet_result(out, spec.max_iters, dtype)
    T_round = jnp.broadcast_to(jnp.asarray(problem.deadline, dtype), (C,)) \
        / jnp.asarray(sysp.global_rounds, dtype)
    alloc0 = init if init is not None else jax.vmap(
        lambda sysc: initial_allocation(
            sysc, bandwidth_frac=problem.bandwidth_frac))(sysp)
    fn = _fleet_fixed_cell_fn(acc, spec.max_iters, spec.tol,
                              spec.sp2_method, spec.sp2_iters)
    out = jax.vmap(fn)(sysp, warr, T_round, alloc0)
    return _fleet_result(out, spec.max_iters, dtype, cols=_FIXED_COLS)


def _fleet_problem(case: str) -> Problem:
    ws = [Weights(0.9, 0.1, 1.0), Weights(0.5, 0.5, 10.0),
          Weights(0.2, 0.8, 3.0), Weights(0.6, 0.4, 0.5)]
    if case == "padded":
        # ragged pools padded to one bucket: masked lanes in every cell
        cells = [pad_system(make_system(jax.random.PRNGKey(30 + i),
                                        n_devices=n), 16)
                 for i, n in enumerate((16, 11, 7, 14))]
        return Problem(system=stack_systems(cells), weights=ws)
    fleet = make_fleet(jax.random.PRNGKey(31), n_cells=4, n_devices=12)
    if case in ("cold", "fixed"):
        init = None
    else:   # warm: start every cell from a drifted fleet's answer
        drifted = fleet.replace(gain=fleet.gain * 1.05)
        init = solve(Problem(system=drifted, weights=ws),
                     SolverSpec(max_iters=4)).allocation
    deadline = (None if case in ("cold", "warm")
                else jnp.asarray([90.0, 120.0, 150.0, 200.0]))
    return Problem(system=fleet, weights=ws, init=init, deadline=deadline,
                   bandwidth_frac=0.5 if case == "fixed" else 1.0)


def _assert_fleets_equal(a, b, exact: bool = True):
    """Allocation, objective, iterations, convergence, ledger and counters
    bit for bit; with `exact` False, the values to 1e-12 and the counts
    that follow from the iterations (BCD iterations, SP1 evaluations)
    exactly."""
    pairs = list(zip(jax.tree_util.tree_leaves(a.allocation),
                     jax.tree_util.tree_leaves(b.allocation)))
    pairs.append((a.objective, b.objective))
    if exact:
        pairs += [(a.history, b.history), (a.counters.data, b.counters.data)]
    else:
        ca, cb = a.counters, b.counters
        for x, y in ((ca.bcd_iters, cb.bcd_iters),
                     (ca.sp1_evals, cb.sp1_evals)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in pairs:
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0 if exact else 1e-12, atol=0,
                                   equal_nan=True)
    assert a.columns == b.columns
    assert np.array_equal(np.asarray(a.iters), np.asarray(b.iters))
    assert np.array_equal(np.asarray(a.converged), np.asarray(b.converged))


@pytest.mark.parametrize("case", ["cold", "warm", "padded", "fixed",
                                  "fixed_warm"])
def test_fleet_program_matches_eager_vmap(case):
    """The cached fleet programs (`_fleet_solve_impl`,
    `_fleet_fixed_solve_impl`) give the eager per-call vmap's answers bit
    for bit — warm `init`, padded lanes and the deadline variant — and a
    free solve gives the region program's on a one-device mesh bit for
    bit.

    The free cold start is the one exception against the eager path: in
    the compiled program its B/N split is a multiply by 1/N, an ulp off
    the eager division at N = 12 (the region program starts the same way).
    That ulp moves the answer by about 1e-14 and can move SP2's evaluation
    count of a single iteration and the last relative step, so there the
    values are held to 1e-12 and the counts that follow from the
    iterations exactly."""
    problem = _fleet_problem(case)
    spec = SolverSpec(max_iters=6, tol=1e-8)
    new = solve(problem, spec)
    iters, history = np.asarray(new.iters), np.asarray(new.history)
    assert iters.min() > 0 and iters.max() > 1
    np.testing.assert_array_equal(   # the objective is the last ledger row's
        np.asarray(new.objective), history[np.arange(4), iters - 1, 0])
    _assert_fleets_equal(_eager_fleet(problem, spec), new,
                         exact=case != "cold")
    if problem.deadline is None:
        region = solve(dataclasses.replace(problem, mesh=region_mesh(1)),
                       spec)
        _assert_fleets_equal(region.fleet, new)


# ---------------------------------------------------------------------------
# per-cell traced weights: the PR 4 fragmentation caveat, closed
# ---------------------------------------------------------------------------

def test_per_cell_weights_match_per_cell_single_solves():
    """A (C, 3) weights stack solves each cell exactly as a single-cell
    solve with that cell's weights — weights are data, not config."""
    fleet = make_fleet(jax.random.PRNGKey(5), n_cells=3, n_devices=12)
    ws = [Weights(0.9, 0.1, 1.0), Weights(0.5, 0.5, 10.0),
          Weights(0.1, 0.9, 30.0)]
    mixed = solve(Problem(system=fleet, weights=ws), SolverSpec(max_iters=6))
    for c, wc in enumerate(ws):
        cell = jax.tree_util.tree_map(lambda x: x[c], fleet)
        single = solve(Problem(system=cell, weights=wc),
                       SolverSpec(max_iters=6))
        assert bool(jnp.all(
            mixed.allocation.bandwidth[c] == single.allocation.bandwidth))
        assert bool(jnp.all(
            mixed.allocation.power[c] == single.allocation.power))
        assert bool(jnp.all(
            mixed.allocation.resolution[c] == single.allocation.resolution))
        assert int(mixed.iters[c]) == single.iters


def test_broadcast_weights_match_shared_weights():
    """Scalar weights broadcast to (C, 3) solve identically to the legacy
    shared-weights path (same compiled program, same values)."""
    fleet = make_fleet(jax.random.PRNGKey(6), n_cells=3, n_devices=10)
    shared = solve(Problem(system=fleet, weights=W), SolverSpec(max_iters=5))
    listed = solve(Problem(system=fleet, weights=[W, W, W]),
                   SolverSpec(max_iters=5))
    assert _tree_equal(shared.allocation, listed.allocation)


def test_weights_array_forms_agree():
    """Raw (3,) arrays and Weights normalize to the same solve."""
    sysp = make_system(jax.random.PRNGKey(8), n_devices=8)
    a = solve(Problem(system=sysp, weights=Weights(1.0, 1.0, 2.0)),
              SolverSpec(max_iters=5))
    b = solve(Problem(system=sysp, weights=jnp.asarray([1.0, 1.0, 2.0])),
              SolverSpec(max_iters=5))
    assert a.objective == pytest.approx(b.objective, rel=1e-12)


# ---------------------------------------------------------------------------
# fixed-deadline satellite: SolverSpec path + max_iters=0 regression
# ---------------------------------------------------------------------------

def test_fixed_deadline_zero_iters_nan_through_solve():
    """max_iters=0 returns the untouched init with a NaN objective (the
    PR 1 IndexError regression), now through the unified path."""
    sysp = make_system(jax.random.PRNGKey(9), n_devices=4)
    res = solve(Problem(system=sysp, weights=Weights(0.99, 0.01, 1.0),
                        deadline=100.0), SolverSpec(max_iters=0))
    assert res.iters == 0
    assert res.history == []
    assert np.isnan(res.objective)
    assert res.allocation.bandwidth.shape == (4,)


def test_fixed_deadline_accepts_spec_options():
    """The deadline variant rides the same SolverSpec path: warm-start
    init and keep_history are honored (the old signature lacked them)."""
    sysp = make_system(jax.random.PRNGKey(10), n_devices=6)
    w = Weights(0.99, 0.01, 0.0)
    cold = solve(Problem(system=sysp, weights=w, deadline=150.0),
                 SolverSpec(max_iters=8))
    warm = solve(Problem(system=sysp, weights=w, deadline=150.0,
                         init=cold.allocation), SolverSpec(max_iters=8))
    assert warm.iters <= cold.iters
    quiet = solve(Problem(system=sysp, weights=w, deadline=150.0),
                  SolverSpec(max_iters=8, keep_history=False))
    assert quiet.history == []
    assert quiet.objective == pytest.approx(cold.objective, rel=1e-12)


# ---------------------------------------------------------------------------
# SolverSpec construction validation (tol floor satellite)
# ---------------------------------------------------------------------------

def test_spec_rejects_tol_below_explicit_dtype_floor():
    floor = rel_step_floor(np.float32)
    with pytest.raises(ValueError, match="64 ulps"):
        SolverSpec(tol=floor / 2, dtype="float32")
    # the same tol is fine under f64
    SolverSpec(tol=floor / 2, dtype="float64")


def test_spec_rejects_tol_below_any_floor():
    with pytest.raises(ValueError, match="float64 rel-step floor"):
        SolverSpec(tol=1e-16)


def test_solve_warns_once_when_tol_below_resolved_floor():
    from repro.api.spec import _TOL_WARNED

    sysp = make_system(jax.random.PRNGKey(11), n_devices=4)
    sys32 = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x).astype(jnp.float32)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, sysp)
    _TOL_WARNED.clear()
    spec = SolverSpec(max_iters=1, tol=2e-6)   # chosen, below the f32 floor
    with pytest.warns(UserWarning, match="rel-step floor"):
        solve(Problem(system=sys32, weights=W), spec)
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)   # second call: silent
        solve(Problem(system=sys32, weights=W), spec)
        # the library DEFAULT tol is exempt (floor-or-1e-6 semantics):
        # a default-configured f32 solve must not warn about a tolerance
        # the user never chose
        solve(Problem(system=sys32, weights=W), SolverSpec(max_iters=1))


def test_weights_leaf_rejects_nonpositive_raw_arrays():
    """Raw arrays share the Weights.normalized() contract: w1 + w2 <= 0
    raises instead of silently normalizing to inf/NaN."""
    from repro import weights_leaf
    with pytest.raises(ValueError, match="must be positive"):
        weights_leaf(jnp.asarray([0.0, 0.0, 1.0]), jnp.float64)
    with pytest.raises(ValueError, match="must be positive"):
        weights_leaf(jnp.asarray([[0.5, 0.5, 1.0], [-1.0, 0.5, 1.0]]),
                     jnp.float64, cells=2)


def test_region_allocator_rejects_spec_plus_legacy_kwargs():
    from repro import RegionAllocator
    with pytest.raises(ValueError, match="not both"):
        RegionAllocator(W, spec=SolverSpec(), tol=1e-3)
    # either form alone is fine
    RegionAllocator(W, spec=SolverSpec(tol=1e-3))
    RegionAllocator(W, tol=1e-3, max_iters=5)


def test_spec_validates_methods_and_iters():
    with pytest.raises(ValueError, match="sp1_method"):
        SolverSpec(sp1_method="newton")
    with pytest.raises(ValueError, match="sp2_method"):
        SolverSpec(sp2_method="cvx")
    with pytest.raises(ValueError, match="max_iters"):
        SolverSpec(max_iters=-1)
    with pytest.raises(ValueError, match="dtype"):
        SolverSpec(dtype="bfloat16")


def test_spec_is_hashable_and_comparable():
    a = SolverSpec(max_iters=8, tol=1e-4)
    b = SolverSpec(max_iters=8, tol=1e-4)
    assert a == b and hash(a) == hash(b)
    assert len({a, b, SolverSpec()}) == 2


def test_spec_dtype_policy_casts_the_solve():
    sysp = make_system(jax.random.PRNGKey(12), n_devices=6)
    res32 = solve(Problem(system=sysp, weights=W),
                  SolverSpec(max_iters=4, tol=1e-4, dtype="float32"))
    assert res32.allocation.bandwidth.dtype == jnp.float32
    res64 = solve(Problem(system=sysp, weights=W),
                  SolverSpec(max_iters=4, tol=1e-4, dtype="float64"))
    assert res64.allocation.bandwidth.dtype == jnp.float64


# ---------------------------------------------------------------------------
# dispatcher routing errors
# ---------------------------------------------------------------------------

def test_dispatcher_rejects_bad_combinations():
    sysp = make_system(jax.random.PRNGKey(13), n_devices=4)
    fleet = make_fleet(jax.random.PRNGKey(13), n_cells=2, n_devices=4)
    with pytest.raises(ValueError, match="needs problem.key"):
        solve(Problem(system=sysp, weights=W, rounds=RoundsConfig(rounds=2)))
    with pytest.raises(ValueError, match="stacked"):
        solve(Problem(system=sysp, weights=W, mesh=region_mesh()))
    # mesh + deadline used to be NotImplementedError; it now shards the
    # fixed-deadline fleet solve (parity-tested in tests/test_region.py)
    reg = solve(Problem(system=fleet, weights=W, deadline=100.0,
                        mesh=region_mesh()), SolverSpec(max_iters=2))
    assert reg.stats["cells"] == 2
    # a deadline on a single cell still cannot take a mesh
    with pytest.raises(ValueError, match="stacked"):
        solve(Problem(system=sysp, weights=W, deadline=100.0,
                      mesh=region_mesh()))
    with pytest.raises(ValueError, match="cell axis"):
        solve(Problem(system=sysp, weights=[W, W]))
    # a tuned spec on a rounds problem would be silently ignored — reject
    with pytest.raises(ValueError, match="RoundsConfig"):
        solve(Problem(system=sysp, weights=W, rounds=RoundsConfig(rounds=2),
                      key=jax.random.PRNGKey(0)), SolverSpec(max_iters=3))
    # lockstep picks the mesh execution mode; meshless it would no-op
    with pytest.raises(ValueError, match="lockstep"):
        solve(Problem(system=fleet, weights=W), SolverSpec(lockstep=True))


def test_deprecation_warns_exactly_once_per_shim():
    sysp = make_system(jax.random.PRNGKey(14), n_devices=4)
    _reset_deprecation_registry()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        allocate(sysp, W, max_iters=1)
        allocate(sysp, W, max_iters=1)
    dep = [r for r in rec if issubclass(r.category, DeprecationWarning)
           and "allocate()" in str(r.message)]
    assert len(dep) == 1
