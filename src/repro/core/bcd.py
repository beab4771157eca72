"""Algorithm 2: full BCD resource-allocation loop (paper §V-D).

The outer loop is a single jitted `lax.while_loop` with an on-device
convergence check: no Python-level `float()` / `.tolist()` syncs inside the
iteration. Per-iteration metrics accumulate into a fixed-size traced ledger
(one row per iteration) that is materialized into `BCDResult.history`
exactly once, after the loop finishes. Because the whole solve is one traced
computation, it `vmap`s across base-station cells.

This module now holds the jitted *impls* plus the shared result types; the
drivers live behind the unified entry point `repro.solve(Problem, SolverSpec)`
(`repro.api.solve`). The historical signatures `allocate` /
`allocate_fixed_deadline` / `allocate_fleet` remain as thin deprecation
shims over it — same results, bit-identical, one `DeprecationWarning` per
process. Objective weights are a traced `(3,)` (per cell) operand of
`_allocate_impl`, never part of the jit-cache key.

Both impls name their blocks with `jax.named_scope`: the loop `bcd`, the
resolution/frequency block `sp1` and the power/bandwidth block `sp2`. The
names exist only at trace time and ride into every compiled op's name
stack (the `tf_op` of a device trace), on every path that reaches the
impls: fleet vmap, region shard_map, pipeline batches, rounds and `diff`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs

from . import energy as en
from .accuracy import AccuracyModel
from .energy import rate as _rate
from .sp1 import _solve_sp1_fixed_impl, sp1_engine
from .sp2 import _golden_argmin, _sp2_direct_impl, _sp2_jong_core, r_min
from .types import Allocation, SystemParams, Weights

Array = jnp.ndarray

# ledger column order (one row per BCD iteration). sp2_iters: Jong outer
# iterations for sp2_method="jong"; for "direct" it carries the measured
# dE/dB evaluation count of the carried-bracket dual search (compare
# against sp2.direct_eval_counts for the non-carried reference).
_LEDGER_COLS = ("objective", "energy", "time", "accuracy",
                "sp2_iters", "sp2_residual", "rel_step")
_FIXED_COLS = ("energy", "time", "accuracy", "sp2_evals", "rel_step")

# solver-effort counter order (SolveCounters.data last axis): BCD outer
# iterations, SP1 dual (Sigma-lambda(T) candidate) evaluations, SP2 dual
# evaluations (dE/dB evals for "direct" / Jong outer iterations for
# "jong"), and the final relative-step convergence residual.
_COUNTER_COLS = ("bcd_iters", "sp1_evals", "sp2_evals", "residual")


@dataclasses.dataclass
class SolveCounters:
    """Device-resident solver-effort counters for one solve.

    `data` is a `(len(columns),)` array for a single-cell solve, `(C,
    len(columns))` for fleet/region results — computed inside the jitted
    solve from the iteration ledger, so constructing this object adds no
    host sync and no compiled shapes. Reading `as_dict()` (or numpy-ing
    `data`) is the one deliberate device->host transfer; the serving hot
    path never takes it. `repro.obs` feeds these into per-request events.
    """
    data: Array
    columns: tuple = _COUNTER_COLS

    def col(self, name: str) -> Array:
        """One counter by name, still on device; leading cell axis kept."""
        return self.data[..., self.columns.index(name)]

    @property
    def bcd_iters(self) -> Array:
        return self.col("bcd_iters")

    @property
    def sp1_evals(self) -> Array:
        return self.col("sp1_evals")

    @property
    def sp2_evals(self) -> Array:
        return self.col("sp2_evals")

    @property
    def residual(self) -> Array:
        return self.col("residual")

    def as_dict(self) -> dict:
        """{name: float | (C,) ndarray} — one blocking transfer."""
        vals = np.asarray(self.data)
        out = {}
        for i, c in enumerate(self.columns):
            v = vals[..., i]
            out[c] = float(v) if v.ndim == 0 else v
        return out


@dataclasses.dataclass
class BCDResult:
    allocation: Allocation
    objective: float
    history: List[dict]
    iters: int
    converged: bool
    counters: Optional[SolveCounters] = None


@dataclasses.dataclass
class FleetResult:
    """Batched BCD solve across C independent base-station cells.

    All leaves carry a leading cell axis: allocation arrays are (C, N),
    per-cell scalars are (C,). `history` is the raw iteration ledger
    (C, max_iters, len(columns)); rows past a cell's `iters` are NaN.
    """
    allocation: Allocation   # (C, N) leaves
    objective: Array         # (C,)
    iters: Array             # (C,) int32
    converged: Array         # (C,) bool
    history: Array           # (C, max_iters, len(columns))
    columns: tuple = _LEDGER_COLS
    counters: Optional[SolveCounters] = None   # (C, 4) device counters


def initial_allocation(sys: SystemParams, key: Optional[jax.Array] = None,
                       bandwidth_frac: float = 1.0, xp=jnp) -> Allocation:
    """Feasible start: p = pmax, B = B/N (paper init; Fig. 9 uses B/(2N)).

    On a padded system (`sys.active` set) the bandwidth split divides by the
    ACTIVE device count and pad lanes start at B = 0, so the active prefix
    of a padded solve starts (and therefore iterates) bit-identically to the
    unpadded one.

    `xp` picks the array namespace (default jnp). The region planning
    layer passes numpy so the init is assembled host-side without touching
    the device stream — full/where/one scalar divide are IEEE-exact
    elementwise ops, so both namespaces are bit-identical."""
    n = sys.n
    if sys.active is None:
        bw = xp.full((n,), sys.bandwidth_total / n * bandwidth_frac)
    else:
        n_eff = xp.sum(xp.asarray(sys.active).astype(
            xp.asarray(sys.gain).dtype))
        # n_eff == 0 (all-inactive filler cell) divides to inf, masked to
        # 0 by the where below — identical in both namespaces, but numpy
        # warns where jnp is silent
        with np.errstate(divide="ignore"):
            share = sys.bandwidth_total / n_eff * bandwidth_frac
        bw = xp.where(sys.active, share,
                      xp.zeros((), xp.asarray(share).dtype))
    return Allocation(
        bandwidth=bw,
        power=xp.full((n,), sys.p_max),
        freq=xp.full((n,), sys.f_max),
        resolution=xp.full((n,), sys.s_lo),
    )


def _init_carry_state(sys: SystemParams, alloc: Allocation):
    """(B, p, f, s, s_hat, T) arrays for the while_loop carry."""
    dtype = jnp.asarray(alloc.bandwidth).dtype
    s_hat = alloc.s_relaxed if alloc.s_relaxed is not None else alloc.resolution
    T = alloc.T if alloc.T is not None else jnp.zeros((), dtype)
    return (alloc.bandwidth, alloc.power, alloc.freq, alloc.resolution,
            jnp.asarray(s_hat), jnp.asarray(T, dtype))


def _bcd_while(state0, max_iters: int, ncols: int, tol, step, mask=None):
    """Shared BCD driver: fixed-size NaN ledger, on-device convergence on the
    relative (B, p, f, s) step, one `lax.while_loop`. `step(state)` performs
    one block-coordinate update and returns (new_state, metric scalars); the
    driver appends the rel-step column and writes the ledger row.

    The tolerance is floored at 64 ulps of the carry dtype: in f32 the
    iterate movement plateaus around ~10 eps (solver bracketing noise, not
    progress), so the old raw tol=1e-6 sat exactly at the noise floor and
    fleet cells reported "not converged" forever — the 12/64 fleet
    convergence-rate bug. Movement below the floor is numerical noise.

    `mask` (an (N,) bool, `sys.active`) zeroes padded-out devices in the
    rel-step norms: their (constant) iterates would otherwise inflate the
    denominator and desync the convergence trajectory from the unpadded
    solve. Returns (*state, iters, converged, ledger)."""
    dtype = state0[0].dtype
    m4 = None if mask is None else jnp.concatenate([mask] * 4)

    def flat(state):
        v = jnp.concatenate([state[0], state[1], state[2], state[3]])
        return v if m4 is None else jnp.where(m4, v, jnp.zeros((), dtype))

    ledger0 = jnp.full((max_iters, ncols), jnp.nan, dtype)
    if max_iters == 0:   # nothing to iterate: return the start point untouched
        return (*state0, jnp.zeros((), jnp.int32), jnp.zeros((), bool), ledger0)
    tol = jnp.maximum(jnp.asarray(tol, dtype), 64.0 * jnp.finfo(dtype).eps)
    prev0 = flat(state0)

    def cond(c):
        k, _, _, conv, _ = c
        return (k < max_iters) & (~conv)

    def body(c):
        k, state, prev, _, ledger = c
        state, metrics = step(state)
        cur = flat(state)
        rel = jnp.linalg.norm(cur - prev) \
            / jnp.maximum(jnp.linalg.norm(prev), 1e-12)
        row = jnp.stack([*(m.astype(dtype) for m in metrics),
                         rel.astype(dtype)])
        ledger = ledger.at[k].set(row)
        return k + 1, state, cur, rel <= tol, ledger

    k, state, _, conv, ledger = lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), state0, prev0,
                     jnp.zeros((), bool), ledger0))
    return (*state, k, conv, ledger)


def _pack_counters(iters, ledger, max_iters: int, sp2_col: int,
                   rel_col: int, sp1_per_iter: int):
    """(len(_COUNTER_COLS),) device array of solver-effort counters,
    reduced from the iteration ledger inside the traced solve — pure
    device ops on values the ledger already carries, so surfacing the
    counters adds no host syncs and no new compiled shapes.

    `sp1_per_iter` is the statically-known SP1 dual-eval count per BCD
    iteration (`sp1.dual_evals_per_iter`; 0 for the closed-form fixed-T
    subproblem) — the sweep/bisect grids have fixed trip counts, so the
    total is exactly `iters * sp1_per_iter`. NaN ledger rows (beyond
    `iters`) drop out of the nansum; residual is the rel-step of the last
    executed iteration (NaN when nothing ran)."""
    dtype = ledger.dtype
    it = iters.astype(dtype)
    sp1 = it * sp1_per_iter
    if max_iters > 0:
        sp2 = jnp.nansum(ledger[:, sp2_col]).astype(dtype)
        last = jnp.clip(iters.astype(jnp.int32) - 1, 0, max_iters - 1)
        residual = jnp.where(iters > 0, ledger[last, rel_col], jnp.nan)
    else:
        sp2 = jnp.zeros((), dtype)
        residual = jnp.full((), jnp.nan, dtype)
    return jnp.stack([it, sp1, sp2, residual.astype(dtype)])


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp1_method",
                                   "sp2_method", "sp2_iters", "kernel"))
def _allocate_impl(sys: SystemParams, warr: Array, acc: AccuracyModel,
                   state0, max_iters: int, tol,
                   sp1_method: str, sp2_method: str, sp2_iters: int,
                   kernel: str):
    """Device-resident Algorithm 2. Returns
    (B, p, f, s, s_hat, T, iters, converged, ledger, counters) — the
    trailing `counters` is the packed `_COUNTER_COLS` effort vector.
    `kernel` is the SP1 sweep kernel's mode, resolved by the caller outside
    jit (`kernels.ops.kernel_mode`)."""
    from .sp1 import dual_evals_per_iter

    dtype = state0[0].dtype
    warr_sp1 = jnp.stack([warr[0], jnp.maximum(warr[1], 1e-9), warr[2]])
    solve_sp1 = sp1_engine(sp1_method, kernel)

    def step(state):
        B, p, _, _, _, _ = state
        tt = sys.bits / jnp.maximum(_rate(sys, B, p), 1e-12)
        with jax.named_scope("sp1"):
            f, s, s_hat, T = solve_sp1(sys, warr_sp1, acc, tt)
        with jax.named_scope("sp2"):
            rmin = r_min(sys, f, s, T)
            if sp2_method == "direct":
                # sp2_iters ledger column = measured dE/dB eval count of
                # the carried-bracket dual search (vs
                # `sp2.direct_eval_counts`)
                p_new, B_new, ev = _sp2_direct_impl(sys, rmin)
                sp2_it = ev.astype(dtype)
                sp2_res = jnp.zeros((), dtype)
            else:
                p_new, B_new, _, _, it2, res2 = _sp2_jong_core(
                    sys, warr[0], rmin, p, B, max_iters=sp2_iters)
                sp2_it = it2.astype(dtype)
                sp2_res = res2.astype(dtype)
        w = Weights(warr[0], warr[1], warr[2])
        alloc = Allocation(bandwidth=B_new, power=p_new, freq=f, resolution=s,
                           s_relaxed=s_hat, T=T)
        metrics = (en.objective(sys, w, acc, alloc),
                   en.total_energy(sys, alloc),
                   en.total_time(sys, alloc),
                   en.total_accuracy(acc, alloc, sys.active),
                   sp2_it, sp2_res)
        return (B_new, p_new, f, s, s_hat, T), metrics

    with jax.named_scope("bcd"):
        out = _bcd_while(state0, max_iters, len(_LEDGER_COLS), tol, step,
                         mask=sys.active)
    counters = _pack_counters(out[6], out[8], max_iters,
                              _LEDGER_COLS.index("sp2_iters"),
                              _LEDGER_COLS.index("rel_step"),
                              dual_evals_per_iter(sp1_method, acc))
    return (*out, counters)


def _materialize_history(ledger: np.ndarray, iters: int,
                         cols: Sequence[str]) -> List[dict]:
    out = []
    for i in range(iters):
        row = dict(iter=i + 1)
        for c, v in zip(cols, ledger[i]):
            row[c] = int(v) if c in ("sp2_iters", "sp2_evals") else float(v)
        out.append(row)
    return out


def allocate(sys: SystemParams, w: Weights, acc: Optional[AccuracyModel] = None,
             max_iters: int = 20, tol: float = 1e-6,
             init: Optional[Allocation] = None,
             sp2_iters: int = 30, sp2_method: str = "direct",
             sp1_method: str = "sweep",
             keep_history: bool = True) -> BCDResult:
    """Deprecated shim: Algorithm 2 through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys, weights=w, acc=acc, init=init),
    SolverSpec(max_iters=..., tol=..., ...))`` — bit-identical results, one
    `DeprecationWarning` per process.
    """
    from repro.api import Problem, SolverSpec, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("allocate", "Problem(system, weights), SolverSpec(...)")
    return solve(Problem(system=sys, weights=w, acc=acc, init=init),
                 SolverSpec(max_iters=max_iters, tol=tol,
                            sp1_method=sp1_method, sp2_method=sp2_method,
                            sp2_iters=sp2_iters, keep_history=keep_history))


def _optimal_split(sys: SystemParams, s: Array, bandwidth: Array,
                   T_round: Array, iters: int = 48) -> Array:
    """Per-device golden-section over the transmission-time share tt of the
    round deadline:  E(tt) = kappa cyc^3 / (T-tt)^2 + E_trans_min(tt | B),
    both terms convex. Returns tt* clipped to the feasible window."""
    cyc = sys.local_iters * sys.zeta * s ** 2 * sys.cycles * sys.samples

    def energy(tt):
        f = jnp.clip(cyc / jnp.maximum(T_round - tt, 1e-9), sys.f_min, sys.f_max)
        e_cmp = sys.kappa * cyc * f ** 2
        r_req = sys.bits / jnp.maximum(tt, 1e-9)
        theta = jnp.exp2(r_req / jnp.maximum(bandwidth, 1e-9)) - 1.0
        p = jnp.clip(theta * sys.noise_psd * bandwidth / sys.gain,
                     sys.p_min, sys.p_max)
        return e_cmp + p * tt

    tt_min = sys.bits / jnp.maximum(
        bandwidth * jnp.log2(1.0 + sys.gain * sys.p_max
                             / (sys.noise_psd * jnp.maximum(bandwidth, 1e-9))),
        1e-12)
    a0 = jnp.minimum(tt_min, 0.95 * T_round)
    b0 = jnp.broadcast_to(jnp.asarray(0.95 * T_round, a0.dtype), a0.shape)
    tt = _golden_argmin(energy, a0, b0, iters=iters)
    return jnp.clip(tt, tt_min, 0.95 * T_round)


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp2_method",
                                   "sp2_iters"))
def _allocate_fixed_impl(sys: SystemParams, warr: Array, acc: AccuracyModel,
                         T_round, state0, max_iters: int, tol,
                         sp2_method: str = "direct", sp2_iters: int = 30):
    """Device-resident deadline-constrained BCD (Figs. 8-9 variant).

    Takes the same SolverSpec-sourced sp2 options as `_allocate_impl`
    (`sp1_method` does not apply: the fixed-T subproblem has no T search to
    sweep or bisect, `_solve_sp1_fixed_impl` is closed-form)."""
    dtype = state0[0].dtype

    def step(state):
        B, p, _, _, s_hat, _ = state
        tt = sys.bits / jnp.maximum(_rate(sys, B, p), 1e-12)
        with jax.named_scope("sp1"):
            f, s = _solve_sp1_fixed_impl(sys, warr, acc, tt, T_round)
        with jax.named_scope("sp2"):
            # Break the BCD split deadlock: with a hard deadline, SP1 pins
            # t_cmp = T - t_trans(current p, B), so SP2's rate floor equals
            # the current rate and (p, B) can never move. Re-derive the
            # floor from the per-device OPTIMAL compute/transmit split
            # (convex in t_trans: E_cmp = kappa cyc^3/(T-tt)^2 rises,
            # E_trans falls; golden section).
            tt_opt = _optimal_split(sys, s, B, T_round)
            rmin = sys.bits / tt_opt
            if sp2_method == "direct":
                p_new, B_new, ev = _sp2_direct_impl(sys, rmin)
                sp2_ev = ev.astype(dtype)
            else:
                p_new, B_new, _, _, it2, _ = _sp2_jong_core(
                    sys, warr[0], rmin, p, B, max_iters=sp2_iters)
                sp2_ev = it2.astype(dtype)
        # recompute f against the achieved transmission time
        tt_new = sys.bits / jnp.maximum(_rate(sys, B_new, p_new), 1e-12)
        cyc = sys.local_iters * sys.zeta * s ** 2 * sys.cycles * sys.samples
        f = jnp.clip(cyc / jnp.maximum(T_round - tt_new, 1e-9),
                     sys.f_min, sys.f_max)
        alloc = Allocation(bandwidth=B_new, power=p_new, freq=f, resolution=s,
                           T=jnp.asarray(T_round, dtype))
        metrics = (en.total_energy(sys, alloc),
                   en.total_time(sys, alloc),
                   en.total_accuracy(acc, alloc, sys.active),
                   sp2_ev)
        return (B_new, p_new, f, s, s_hat,
                jnp.asarray(T_round, dtype)), metrics

    # sp1_per_iter = 0: _solve_sp1_fixed_impl enumerates the discrete
    # resolution menu in closed form — no dual search to count
    with jax.named_scope("bcd"):
        out = _bcd_while(state0, max_iters, len(_FIXED_COLS), tol, step,
                         mask=sys.active)
    counters = _pack_counters(out[6], out[8], max_iters,
                              _FIXED_COLS.index("sp2_evals"),
                              _FIXED_COLS.index("rel_step"), 0)
    return (*out, counters)


def allocate_fixed_deadline(sys: SystemParams, w: Weights, T_total: float,
                            acc: Optional[AccuracyModel] = None,
                            max_iters: int = 20, tol: float = 1e-6,
                            init: Optional[Allocation] = None,
                            bandwidth_frac: float = 1.0,
                            sp2_iters: int = 30, sp2_method: str = "direct",
                            keep_history: bool = True) -> BCDResult:
    """Deprecated shim: the deadline-constrained variant through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys, weights=w, deadline=T_total,
    ...), SolverSpec(...))``. Now wired through the same SolverSpec path as
    every other entry point, so it accepts the warm-start ``init`` and the
    sp2 engine options the free-deadline solver grew (the fixed-T
    subproblem has no T search, so ``sp1_method`` does not apply).
    """
    from repro.api import Problem, SolverSpec, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("allocate_fixed_deadline",
                     "Problem(system, weights, deadline=T_total), "
                     "SolverSpec(...)")
    return solve(Problem(system=sys, weights=w, acc=acc, init=init,
                         deadline=T_total, bandwidth_frac=bandwidth_frac),
                 SolverSpec(max_iters=max_iters, tol=tol,
                            sp2_method=sp2_method, sp2_iters=sp2_iters,
                            keep_history=keep_history))


# ----------------------------------------------------------------------------
# Fleet-scale batched allocation (beyond paper): one vmap'd BCD solve across
# C independent base-station cells — the ROADMAP path to millions of clients.
# ----------------------------------------------------------------------------

def stack_systems(systems: Sequence[SystemParams], xp=jnp) -> SystemParams:
    """Stack per-cell SystemParams into one batched pytree: per-device arrays
    become (C, N), per-cell scalars become (C,). Cells may differ in any
    numeric scalar (bandwidth_total, p_max, ... are traced leaves), so mixed
    cell classes batch through one vmap'd solve; only the static aux data —
    the discrete resolution menu — must match across cells.

    Pad-safe: if any cell carries an `active` mask (`pad_system`), cells
    without one get an all-True mask so the pytree structures agree — a
    bucketed batch may mix padded and exactly-sized cells."""
    from .types import _SYS_STATIC

    aux = tuple(getattr(systems[0], k) for k in _SYS_STATIC)
    for s_ in systems[1:]:
        if tuple(getattr(s_, k) for k in _SYS_STATIC) != aux:
            raise ValueError(
                "stack_systems: cells differ in static config (resolutions)")
    if any(s_.active is not None for s_ in systems):
        systems = [s_ if s_.active is not None else
                   s_.replace(active=xp.ones(xp.asarray(s_.gain).shape,
                                             bool))
                   for s_ in systems]
    return jax.tree_util.tree_map(lambda *xs: xp.stack(xs), *systems)


def _fleet_cell_fn(acc, max_iters, tol, sp1_method, sp2_method,
                   sp2_iters, kernel, with_init: bool):
    """Per-cell solver closure shared by the fleet program and the region
    shard_map (`_fleet_solve_impl` / `region.mesh._region_solve_impl`).
    The weights array is a *vmapped operand* — each cell carries its own
    traced (3,) row of a (C, 3) stack, so per-cell/per-request weights
    share one compiled program."""
    def warm(sysc, warr_c, alloc0):
        state0 = _init_carry_state(sysc, alloc0)
        return _allocate_impl(sysc, warr_c, acc, state0, max_iters, tol,
                              sp1_method, sp2_method, sp2_iters, kernel)

    if with_init:
        return warm
    return lambda sysc, warr_c: warm(sysc, warr_c,
                                     initial_allocation(sysc))


def _fleet_fixed_cell_fn(acc, max_iters, tol, sp2_method, sp2_iters):
    """Per-cell deadline-constrained solver closure for the fleet program
    (`_fleet_fixed_solve_impl`): the fixed-T sibling of
    `_fleet_cell_fn`. The per-round deadline rides as a vmapped per-cell
    scalar operand, so heterogeneous deadlines (or heterogeneous
    `global_rounds`) share one compiled program."""
    def fn(sysc, warr_c, T_round_c, alloc0):
        state0 = _init_carry_state(sysc, alloc0)
        return _allocate_fixed_impl(sysc, warr_c, acc, T_round_c, state0,
                                    max_iters, tol, sp2_method, sp2_iters)
    return fn


def _fleet_objective(iters, ledger, max_iters: int, dtype) -> Array:
    """Per-cell objective of stacked solves: ledger column 0 at row
    `iters - 1` (the objective for both column sets, "objective" free /
    "energy" fixed), NaN where a cell ran no iteration. Pure array ops, so
    it runs inside the fleet programs and eagerly on mesh outputs alike."""
    if max_iters > 0:
        idx = jnp.clip(iters.astype(jnp.int32) - 1, 0, max_iters - 1)
        last = jnp.take_along_axis(ledger[..., 0], idx[:, None], axis=1)[:, 0]
        return jnp.where(iters > 0, last, jnp.nan)
    return jnp.full(iters.shape, jnp.nan, dtype)


def _fleet_assemble(out, cols: Sequence[str] = _LEDGER_COLS) -> FleetResult:
    """Wrap the stacked raw solve outputs plus the per-cell objective
    (`_fleet_objective`, appended last) in a FleetResult — dataclasses
    only, no device op."""
    B, p, f, s, s_hat, T, iters, conv, ledger, counters, objective = out
    allocation = Allocation(bandwidth=B, power=p, freq=f, resolution=s,
                            s_relaxed=s_hat if cols is _LEDGER_COLS else None,
                            T=T)
    return FleetResult(allocation=allocation, objective=objective,
                       iters=iters, converged=conv, history=ledger,
                       columns=tuple(cols),
                       counters=SolveCounters(data=counters))


def _fleet_result(out, max_iters: int, dtype,
                  cols: Sequence[str] = _LEDGER_COLS) -> FleetResult:
    """Assemble a FleetResult from the stacked raw `_allocate_impl` (or
    `_allocate_fixed_impl`, with cols=_FIXED_COLS) outputs — all leaves
    carry a leading cell axis. The objective is selected in eager ops: the
    mesh paths, whose programs return the raw outputs, call this."""
    return _fleet_assemble(
        (*out, _fleet_objective(out[6], out[8], max_iters, dtype)), cols)


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp1_method",
                                   "sp2_method", "sp2_iters", "kernel",
                                   "with_init"))
def _fleet_solve_impl(sys_batch: SystemParams, warr: Array, init, tol,
                      acc: AccuracyModel, max_iters: int, sp1_method: str,
                      sp2_method: str, sp2_iters: int, kernel: str,
                      with_init: bool):
    """The one-device fleet solve as one compiled program, cached on the
    shapes and the static solver options: the cold start (unless
    `with_init`), the vmapped `_fleet_cell_fn` and the per-cell objective.
    `warr` is the traced (C, 3) weights stack and `tol` a traced scalar.
    The cold start is computed inside the program, as in
    `region.mesh._region_solve_impl`, so both paths start from the same
    bits (compiled, the B/N split is a multiply by 1/N: an ulp off an eager
    division). Returns the raw stacked outputs with the objective appended
    (`_fleet_assemble`). The body runs only when traced, so the
    `solve_fleet_traces` counter stays flat while the cache holds."""
    obs.counter("solve_fleet_traces").inc()
    fn = _fleet_cell_fn(acc, max_iters, tol, sp1_method, sp2_method,
                        sp2_iters, kernel, with_init)
    args = (sys_batch, warr, init) if with_init else (sys_batch, warr)
    out = jax.vmap(fn)(*args)
    return (*out, _fleet_objective(out[6], out[8], max_iters,
                                   sys_batch.gain.dtype))


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp2_method",
                                   "sp2_iters"))
def _fleet_fixed_solve_impl(sys_batch: SystemParams, warr: Array, T_round,
                            alloc0: Allocation, tol, acc: AccuracyModel,
                            max_iters: int, sp2_method: str, sp2_iters: int):
    """Deadline-constrained sibling of `_fleet_solve_impl`: the per-cell
    per-round deadline `T_round` (C,) is a traced operand. The start point
    `alloc0` comes from the caller, as in `region.mesh._region_fixed_impl`,
    so both paths start from the same bits. Counted in
    `solve_fleet_fixed_traces`."""
    obs.counter("solve_fleet_fixed_traces").inc()
    fn = _fleet_fixed_cell_fn(acc, max_iters, tol, sp2_method, sp2_iters)
    out = jax.vmap(fn)(sys_batch, warr, T_round, alloc0)
    return (*out, _fleet_objective(out[6], out[8], max_iters,
                                   sys_batch.gain.dtype))


def allocate_fleet(sys_batch: SystemParams, w: Weights,
                   acc: Optional[AccuracyModel] = None,
                   max_iters: int = 20, tol: float = 1e-6,
                   init: Optional[Allocation] = None,
                   sp2_iters: int = 30,
                   sp2_method: str = "direct",
                   sp1_method: str = "sweep") -> FleetResult:
    """Deprecated shim: batched Algorithm 2 through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys_batch, weights=w, ...),
    SolverSpec(...))`` on a stacked (C, N) system (`stack_systems` /
    `make_fleet`). The new path also takes per-cell weights — pass a
    sequence of `Weights` (or a (C, 3) array) as `Problem.weights`.
    To shard the cell axis across a device mesh, set `Problem.mesh`.
    """
    from repro.api import Problem, SolverSpec, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("allocate_fleet",
                     "Problem(system=sys_batch, weights), SolverSpec(...)")
    return solve(Problem(system=sys_batch, weights=w, acc=acc, init=init),
                 SolverSpec(max_iters=max_iters, tol=tol,
                            sp1_method=sp1_method, sp2_method=sp2_method,
                            sp2_iters=sp2_iters))
