"""`solve(problem, spec)` — the one entry point to Algorithm 2.

The repo grew seven divergent solver signatures (`allocate`,
`allocate_fixed_deadline`, `allocate_fleet`, `allocate_region`,
`run_rounds`, `run_rounds_fleet`/`run_rounds_region`, plus the
`RegionAllocator` kwargs), each re-threading the same static options into
the jitted impls. `solve` collapses that 4x2 entry-point matrix to one
code path that routes on `Problem` topology:

    single cell        -> BCD (`BCDResult`)
    (C, N) stack       -> fleet program, a jitted vmap (`FleetResult`)
    + mesh             -> region shard_map (`RegionResult`)
    + rounds config    -> round-dynamics scan (`RoundsResult`)
    + deadline         -> deadline-constrained BCD (`BCDResult`; on a
                          (C, N) stack a fleet program with per-cell
                          deadlines -> `FleetResult`; + mesh a sharded
                          region solve -> `RegionResult`)
    + assoc config     -> BCD-over-association outer loop on a stacked
                          cross-cell system (`assoc.AssocResult`)

Weights enter the jitted solvers as a traced ``(3,)`` / ``(C, 3)`` operand
(`api.problem.weights_leaf`), so per-cell / per-request weights cost zero
extra compiles; `SolverSpec` (+ shapes) is the entire jit-cache key.

The legacy signatures survive as thin deprecation shims over this module —
each warns `DeprecationWarning` once per process and delegates verbatim, so
results are bit-identical by construction.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.accuracy import default_accuracy
from repro.core.bcd import (_FIXED_COLS, _LEDGER_COLS, _allocate_fixed_impl,
                            _allocate_impl, _fleet_assemble,
                            _fleet_fixed_solve_impl, _fleet_result,
                            _fleet_solve_impl, _init_carry_state,
                            _materialize_history, BCDResult,
                            SolveCounters, initial_allocation)
from repro.core.types import Allocation, SystemParams
from repro.kernels.ops import kernel_mode

from .problem import Problem, weights_leaf
from .spec import SolverSpec, warn_tol_floor

Array = jnp.ndarray

# ---------------------------------------------------------------------------
# deprecation shims: one warning per legacy entry point per process
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set = set()


def _warn_deprecated(name: str, replacement: str) -> None:
    """Warn once per process that `name` is a legacy shim over `solve`."""
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"repro: {name}() is a deprecated shim; use "
        f"repro.solve({replacement}) — see the migration table in the "
        f"repro package docstring.", DeprecationWarning, stacklevel=3)


def _reset_deprecation_registry() -> None:
    """Testing hook: make every shim warn again."""
    _DEPRECATION_WARNED.clear()


# ---------------------------------------------------------------------------
# dtype policy
# ---------------------------------------------------------------------------

def _cast_tree(tree, dtype):
    """Cast every floating leaf to `dtype` (bool masks / int leaves kept)."""
    def cast(x):
        x = jnp.asarray(x)
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
    return jax.tree_util.tree_map(cast, tree)


def _apply_dtype(system: SystemParams, init: Optional[Allocation],
                 dtype: Optional[str]):
    if dtype is None:
        return system, init
    return (_cast_tree(system, dtype),
            None if init is None else _cast_tree(init, dtype))


# ---------------------------------------------------------------------------
# the dispatcher
# ---------------------------------------------------------------------------

def _topology_label(problem: Problem) -> str:
    """Deterministic topology tag for the solve span (shape metadata only —
    `np.ndim` reads `.ndim` and never syncs or copies to the device)."""
    if problem.assoc is not None:
        return "assoc"
    base = ("rounds" if problem.rounds is not None
            else "fixed" if problem.deadline is not None else "bcd")
    if problem.mesh is not None:
        return base + "_region"
    if np.ndim(problem.system.gain) == 2:
        return base + "_fleet"
    return base


def solve(problem: Problem, spec: Optional[SolverSpec] = None):
    """Solve one `Problem` under one `SolverSpec`; route on topology.

    Returns the per-topology result type (`BCDResult`, `FleetResult`,
    `RegionResult`, or `RoundsResult`) — bit-identical to the legacy entry
    point it replaces (parity-tested in tests/test_api_parity.py).

    The call is a `solve` span (tagged with the routed topology for a
    recorder) whose three children split its host time: `solve.prepare`
    (validation, dtype casts, weights, padding and placement),
    `solve.launch` (tracing and enqueueing the compiled solve) and
    `solve.result` (assembling the result). With no recorder and no
    profiler session each is the cached null span (see tests/test_obs.py
    for the jit-cache guard: the spans change no compiled shapes).
    """
    attrs = {"topology": _topology_label(problem)} if obs.enabled() else {}
    with obs.span("solve", **attrs), _Stages() as stages:
        return _solve_routed(problem, spec, stages)


class _Stages:
    """The child spans of one `solve` call, one open at a time: `to(name)`
    closes the open one and opens `solve.<name>`; leaving the `with`
    closes the last."""

    __slots__ = ("_open",)

    def __enter__(self):
        self._open = None
        return self

    def to(self, name: str) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
        self._open = obs.span("solve." + name)
        self._open.__enter__()

    def __exit__(self, *exc):
        if self._open is not None:
            self._open.__exit__(*exc)
        return False


def _solve_routed(problem: Problem, spec: Optional[SolverSpec],
                  stages: _Stages):
    stages.to("prepare")
    spec = SolverSpec() if spec is None else spec
    cells = problem.cells   # also validates system.gain is 1-D or 2-D
    sysp, init = _apply_dtype(problem.system, problem.init, spec.dtype)
    if problem.rounds is None:
        # rounds problems take their BCD tol from the RoundsConfig instead
        warn_tol_floor(spec.tol, jnp.asarray(sysp.gain).dtype)
    if spec.lockstep and problem.mesh is None:
        # lockstep selects the GSPMD execution mode of a mesh solve; on a
        # meshless problem it would silently do nothing
        raise ValueError("solve: SolverSpec.lockstep requires Problem.mesh")
    if problem.assoc is not None:
        from repro.assoc.loop import solve_assoc

        if problem.rounds is not None or problem.deadline is not None:
            raise ValueError(
                "solve: assoc is exclusive with rounds/deadline (the "
                "association loop owns the outer iteration)")
        if cells is None:
            raise ValueError(
                "solve: assoc requires a stacked (C, N) cross-cell system "
                "(assoc.make_multicell)")
        # the association loop launches and reads back its own solves
        stages.to("launch")
        return solve_assoc(
            dataclasses.replace(problem, system=sysp, init=init), spec)
    if problem.rounds is not None:
        if problem.deadline is not None:
            raise ValueError("solve: rounds and deadline are exclusive")
        if problem.key is None:
            raise ValueError(
                "solve: a rounds problem needs problem.key (PRNG key for "
                "the channel / participation sampling)")
        # the per-round solver options live on RoundsConfig (itself the
        # scan's static jit key); silently dropping a tuned spec here
        # would mislead, so only the fields the rounds paths actually
        # consult (lockstep, dtype) may differ from the defaults
        ref = SolverSpec(lockstep=spec.lockstep, dtype=spec.dtype)
        if spec != ref:
            raise ValueError(
                "solve: a rounds problem takes its BCD options "
                "(bcd_iters/bcd_tol/sp*_method) from the RoundsConfig, "
                "not from SolverSpec — configure problem.rounds instead "
                "(only SolverSpec.lockstep and .dtype apply here)")
        if problem.mesh is not None:
            if cells is None:
                raise ValueError("solve: mesh requires a stacked (C, N) "
                                 "system (stack_systems / make_fleet)")
            return _solve_rounds_region(problem, spec, sysp, init, stages)
        if cells is None:
            return _solve_rounds(problem, spec, sysp, init, stages)
        return _solve_rounds_fleet(problem, spec, sysp, init, stages)
    if problem.deadline is not None:
        if problem.mesh is not None:
            if cells is None:
                raise ValueError("solve: mesh requires a stacked (C, N) "
                                 "system (stack_systems / make_fleet)")
            return _solve_fixed_region(problem, spec, sysp, init, stages)
        if cells is not None:
            return _solve_fixed_fleet(problem, spec, sysp, init, stages)
        return _solve_fixed(problem, spec, sysp, init, stages)
    if problem.mesh is not None:
        if cells is None:
            raise ValueError("solve: mesh requires a stacked (C, N) system "
                             "(stack_systems / make_fleet)")
        return _solve_region(problem, spec, sysp, init, stages)
    if cells is None:
        return _solve_single(problem, spec, sysp, init, stages)
    return _solve_fleet(problem, spec, sysp, init, stages)


# ---------------------------------------------------------------------------
# per-topology drivers (the former entry-point bodies, now the only copy)
# ---------------------------------------------------------------------------

def _bcd_result(out, alloc0, spec: SolverSpec, cols, objective_col: str,
                with_s_relaxed: bool) -> BCDResult:
    """Shared single-cell result assembly: materialize the ledger (or, with
    keep_history=False, pull only the objective scalar — cols[0] is the
    objective column for the free solve and "energy" for the fixed one,
    both at ledger index of `objective_col`), and hand back the untouched
    init when max_iters=0 ran nothing (objective NaN, the PR 1 regression
    contract)."""
    B, pw, f, s, s_hat, T, iters, conv, ledger, counters = out
    iters = int(iters)
    if spec.keep_history:
        history = _materialize_history(np.asarray(ledger), iters, cols)
        objective = history[-1][objective_col] if history else float("nan")
    else:
        history = []
        col = cols.index(objective_col)
        objective = float(ledger[iters - 1, col]) if iters else float("nan")
    allocation = Allocation(bandwidth=B, power=pw, freq=f, resolution=s,
                            s_relaxed=s_hat if with_s_relaxed else None,
                            T=T) if iters else alloc0
    return BCDResult(allocation=allocation, objective=objective,
                     history=history, iters=iters, converged=bool(conv),
                     counters=SolveCounters(data=counters))


def _solve_single(p: Problem, spec: SolverSpec, sysp, init,
                  stages: _Stages) -> BCDResult:
    acc = p.acc if p.acc is not None else default_accuracy()
    alloc0 = init if init is not None else initial_allocation(sysp)
    state0 = _init_carry_state(sysp, alloc0)
    warr = weights_leaf(p.weights, state0[0].dtype)
    stages.to("launch")
    out = _allocate_impl(
        sysp, warr, acc, state0, spec.max_iters, spec.tol,
        spec.sp1_method, spec.sp2_method, spec.sp2_iters, kernel_mode())
    stages.to("result")
    return _bcd_result(out, alloc0, spec, _LEDGER_COLS, "objective",
                       with_s_relaxed=True)


def _solve_fixed(p: Problem, spec: SolverSpec, sysp, init,
                 stages: _Stages) -> BCDResult:
    acc = p.acc if p.acc is not None else default_accuracy()
    T_round = p.deadline / sysp.global_rounds
    alloc0 = init if init is not None else initial_allocation(
        sysp, bandwidth_frac=p.bandwidth_frac)
    state0 = _init_carry_state(sysp, alloc0)
    dtype = state0[0].dtype
    warr = weights_leaf(p.weights, dtype)
    T_round = jnp.asarray(T_round, dtype)
    stages.to("launch")
    out = _allocate_fixed_impl(
        sysp, warr, acc, T_round, state0,
        spec.max_iters, spec.tol, spec.sp2_method, spec.sp2_iters)
    stages.to("result")
    return _bcd_result(out, alloc0, spec, _FIXED_COLS, "energy",
                       with_s_relaxed=False)


def _solve_fixed_fleet(p: Problem, spec: SolverSpec, sysp, init,
                       stages: _Stages):
    """Deadline-constrained BCD vmapped over a stacked (C, N) fleet.

    `Problem.deadline` may be a scalar (one total budget for every cell)
    or a (C,) array of per-cell budgets; either way the per-round deadline
    T_total / global_rounds enters the compiled solve as a traced per-cell
    operand — heterogeneous deadlines never recompile. Returns a
    `FleetResult` with the fixed-variant ledger columns (col 0 "energy" is
    the per-cell objective, matching the single-cell path)."""
    acc = p.acc if p.acc is not None else default_accuracy()
    gain = jnp.asarray(sysp.gain)
    dtype, C = gain.dtype, int(gain.shape[0])
    warr = weights_leaf(p.weights, dtype, cells=C)
    T_round = _per_cell_T_round(p, sysp, C, dtype)
    alloc0 = init if init is not None else jax.vmap(
        lambda sysc: initial_allocation(
            sysc, bandwidth_frac=p.bandwidth_frac))(sysp)
    stages.to("launch")
    out = _fleet_fixed_solve_impl(
        sysp, warr, T_round, alloc0, np.asarray(spec.tol, dtype), acc,
        spec.max_iters, spec.sp2_method, spec.sp2_iters)
    stages.to("result")
    return _fleet_assemble(out, cols=_FIXED_COLS)


def _per_cell_T_round(p: Problem, sysp, C: int, dtype):
    """Per-round deadline (C,) operand: scalar budgets broadcast, (C,)
    budgets pass through — traced either way, never a recompile."""
    deadline = jnp.asarray(p.deadline, dtype)
    if deadline.ndim not in (0, 1) or (deadline.ndim == 1
                                       and deadline.shape[0] != C):
        raise ValueError(
            f"solve: deadline must be a scalar or a ({C},) per-cell "
            f"array, got shape {deadline.shape}")
    return jnp.broadcast_to(deadline, (C,)) \
        / jnp.asarray(sysp.global_rounds, dtype)


def _solve_fixed_region(p: Problem, spec: SolverSpec, sysp, init,
                        stages: _Stages):
    """Deadline-constrained fleet solve sharded over `Problem.mesh`: the
    vmapped `_fleet_fixed_cell_fn` under the region shard_map, exactly the
    free-variant `_solve_region` layout — pad the cell axis to a mesh
    multiple, place, solve (shard-local convergence exit unless
    `SolverSpec.lockstep`), slice. Per-cell results are bit-identical to
    the unsharded `_solve_fixed_fleet` path (sharding moves work, not
    math; parity-tested in tests/test_region.py)."""
    from repro.region.mesh import (RegionResult, _pack_stats,
                                   _region_fixed_impl, _slice_fleet,
                                   pad_cells, place_cells)

    mesh = p.mesh
    acc = p.acc if p.acc is not None else default_accuracy()
    C = int(jnp.asarray(sysp.gain).shape[0])
    D = int(mesh.devices.size)
    Cp = -(-C // D) * D
    dtype = jnp.asarray(sysp.gain).dtype
    T_round = _per_cell_T_round(p, sysp, C, dtype)
    alloc0 = init if init is not None else jax.vmap(
        lambda sysc: initial_allocation(
            sysc, bandwidth_frac=p.bandwidth_frac))(sysp)
    sysb = place_cells(pad_cells(sysp, Cp), mesh)
    warr = place_cells(pad_cells(weights_leaf(p.weights, dtype, cells=C),
                                 Cp), mesh)
    T_b = place_cells(pad_cells(T_round, Cp), mesh)
    alloc0b = place_cells(pad_cells(alloc0, Cp), mesh)
    tol = jnp.asarray(spec.tol, dtype)
    stages.to("launch")
    out = _region_fixed_impl(sysb, warr, T_b, alloc0b, tol, acc,
                             spec.max_iters, spec.sp2_method, spec.sp2_iters,
                             mesh, spec.lockstep)
    stages.to("result")
    fleet = _slice_fleet(
        _fleet_result(out, spec.max_iters, dtype, cols=_FIXED_COLS), C)
    return RegionResult(fleet=fleet,
                        _stats_packed=_pack_stats(fleet, n_shards=D),
                        _n_cells=C, _mesh_devices=D)


def _solve_fleet(p: Problem, spec: SolverSpec, sysp, init,
                 stages: _Stages):
    acc = p.acc if p.acc is not None else default_accuracy()
    gain = jnp.asarray(sysp.gain)
    dtype, C = gain.dtype, int(gain.shape[0])
    warr = weights_leaf(p.weights, dtype, cells=C)
    tol = np.asarray(spec.tol, dtype)
    stages.to("launch")
    out = _fleet_solve_impl(sysp, warr, init, tol, acc, spec.max_iters,
                            spec.sp1_method, spec.sp2_method, spec.sp2_iters,
                            kernel_mode(), init is not None)
    stages.to("result")
    return _fleet_assemble(out)


def _solve_region(p: Problem, spec: SolverSpec, sysp, init,
                  stages: _Stages):
    from repro.region.mesh import (RegionResult, _pack_stats,
                                   _region_solve_impl, _slice_fleet,
                                   pad_cells, place_cells)

    mesh = p.mesh
    acc = p.acc if p.acc is not None else default_accuracy()
    C = int(jnp.asarray(sysp.gain).shape[0])
    D = int(mesh.devices.size)
    Cp = -(-C // D) * D
    dtype = jnp.asarray(sysp.gain).dtype
    sysb = place_cells(pad_cells(sysp, Cp), mesh)
    initb = None if init is None else place_cells(pad_cells(init, Cp), mesh)
    warr = place_cells(pad_cells(weights_leaf(p.weights, dtype, cells=C),
                                 Cp), mesh)
    tol = jnp.asarray(spec.tol, dtype)
    stages.to("launch")
    out = _region_solve_impl(sysb, warr, initb, tol,
                             acc, spec.max_iters, spec.sp1_method,
                             spec.sp2_method, spec.sp2_iters, kernel_mode(),
                             mesh, spec.lockstep, init is not None)
    stages.to("result")
    fleet = _slice_fleet(_fleet_result(out, spec.max_iters, dtype), C)
    return RegionResult(fleet=fleet,
                        _stats_packed=_pack_stats(fleet, n_shards=D),
                        _n_cells=C, _mesh_devices=D)


def _solve_rounds(p: Problem, spec: SolverSpec, sysp, init,
                  stages: _Stages):
    from repro.dynamics.engine import (_check_simulation_init, _result,
                                       _run_rounds_impl)

    acc = p.acc if p.acc is not None else default_accuracy()
    cfg = p.rounds
    _check_simulation_init(cfg, init)
    alloc0 = init if init is not None else initial_allocation(sysp)
    state0 = _init_carry_state(sysp, alloc0)
    warr = weights_leaf(p.weights, state0[0].dtype)
    stages.to("launch")
    out = _run_rounds_impl(sysp, warr, acc, p.key, state0, cfg,
                           kernel_mode())
    stages.to("result")
    return _result(out)


def _solve_rounds_fleet(p: Problem, spec: SolverSpec, sysp, init,
                        stages: _Stages):
    from repro.dynamics.engine import (_check_simulation_init, _result,
                                       _run_rounds_fleet_impl)

    acc = p.acc if p.acc is not None else default_accuracy()
    cfg = p.rounds
    _check_simulation_init(cfg, init)
    dtype = jnp.asarray(sysp.gain).dtype
    C = int(jnp.asarray(sysp.gain).shape[0])
    warr = weights_leaf(p.weights, dtype, cells=C)
    keys = jax.random.split(p.key, C)
    init_state = None if init is None else jax.vmap(_init_carry_state)(
        sysp, init)
    stages.to("launch")
    out = _run_rounds_fleet_impl(sysp, warr, acc, keys, init_state, cfg,
                                 kernel_mode())
    stages.to("result")
    return _result(out)


def _solve_rounds_region(p: Problem, spec: SolverSpec, sysp, init,
                         stages: _Stages):
    from repro.dynamics.config import RoundsResult
    from repro.dynamics.engine import _check_simulation_init, _result
    from repro.region.mesh import (_region_rounds_impl, pad_cells,
                                   place_cells)

    mesh = p.mesh
    acc = p.acc if p.acc is not None else default_accuracy()
    cfg = p.rounds
    _check_simulation_init(cfg, init)
    C = int(jnp.asarray(sysp.gain).shape[0])
    D = int(mesh.devices.size)
    Cp = -(-C // D) * D
    dtype = jnp.asarray(sysp.gain).dtype
    warr = place_cells(pad_cells(weights_leaf(p.weights, dtype, cells=C),
                                 Cp), mesh)
    keys = pad_cells(jax.random.split(p.key, C), Cp)
    sysb = place_cells(pad_cells(sysp, Cp), mesh)
    keysb = place_cells(keys, mesh)
    init_state = None if init is None else jax.vmap(_init_carry_state)(
        sysp, init)
    initb = None if init_state is None else place_cells(
        pad_cells(init_state, Cp), mesh)
    stages.to("launch")
    out = _region_rounds_impl(sysb, warr, keysb, initb, acc, cfg,
                              kernel_mode(), mesh, spec.lockstep,
                              init_state is not None)
    stages.to("result")
    res = _result(out)
    cut = lambda x: x[:C]
    return RoundsResult(
        allocation=jax.tree_util.tree_map(cut, res.allocation),
        ledger=cut(res.ledger), staleness=cut(res.staleness),
        gains=cut(res.gains), resolutions=cut(res.resolutions),
        columns=res.columns)
