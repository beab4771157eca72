"""Implicit KKT gradients through the BCD fixed point (`core/bcd.py`).

The allocator's forward pass is a `lax.while_loop` over block-coordinate
steps x -> Phi(x, theta), x = (B, p), where Phi is one SP1 (f, s, T given
transmission times) + SP2 (p, B given rate floors) sweep and theta collects
the differentiable problem data: the raw weight vector (w1, w2, rho) and any
float `SystemParams` leaves (gain, cycles, bandwidth_total, kappa, ...).
Unrolling that loop for reverse-mode AD would be both expensive (hundreds of
bisection iterations per BCD step) and *wrong* — the inner solves are
fixed-iteration bisections whose iterates have zero derivative.

Instead we differentiate implicitly at the solved point:

* the fixed point is wrapped in a `jax.custom_vjp` whose backward pass
  solves the adjoint system u = v + Phi_x^T u and then pulls u back through
  Phi_theta. The default is a truncated Neumann series (`adjoint_iters`
  applications of the one-step pullback); `adjoint_iters=0` switches to an
  exact dense solve of (I - Phi_x^T) u = v over the (B, p) state (2N
  unknowns). One linearization of Phi serves all four metric cotangents.
* inside Phi, every inner bisection (SP1's nested dual search, SP2's budget
  multiplier, the rate-floor `_b_min`) runs under `stop_gradient` and is
  followed by one Newton/arrowhead correction on the exported stationarity
  residuals (`core.sp1.sp1_stationarity`, `core.sp2.sp2_stationarity`):
  equal in value to solver precision, exact implicit-function-theorem
  derivative.

Subgradient conventions (see ROADMAP "Differentiable allocation"):

* `round_resolution` is piecewise-constant: the discrete s carries zero
  gradient a.e., so the accuracy metric's gradient is the (a.e. correct)
  zero subgradient except through lanes still moving the relaxed s-hat.
* box clips (f, s, p at their bounds) contribute one-sided zero derivatives;
  the makespan/total-time `max` routes gradient to the argmax lane.
* active sets (lam_n > 0 in SP1, B_n above its rate floor in SP2) are frozen
  at the solved point: gradients are exact within the current active set's
  validity region, and at an active-set flip (a nondifferentiable point of
  the true solution map) we return the current set's one-sided derivative.

Saturated-regime caveat. The BCD equilibrium of this model family generically
saturates the bandwidth budget with the fit-scaled rate floors (sum b_min ~
0.999 B_total, power at/near p_max on every lane — the w2*T pressure keeps
re-tightening T until the floors reconsume the budget, at ANY bandwidth
scale). At such fixed points the one-step map has near-unit neutral modes
and the forward program's finite differences include discrete-solver
trajectory effects (the carried-bracket SP2 search freezes each lane at the
budget-bisection step where it converged) that no linearization at the
solved point reproduces. Consequences, measured against central FD of the
full solve in f64: gradients w.r.t. weights and the SP1-side leaves (kappa,
cycles, samples, local_iters, global_rounds, s_standard) agree to ~1e-6;
gradients w.r.t. the channel-side leaves (gain, bits, noise_psd, p_max,
bandwidth_total) are the one-sided KKT derivative and track program FD in
sign and magnitude but only to a few percent. Treat channel-side gradients
as descent directions, not certified sensitivities.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

from ..api.problem import Problem
from ..api.spec import SolverSpec
from ..core import energy as en
from ..core.accuracy import AccuracyModel, default_accuracy
from ..core.bcd import _allocate_impl, _init_carry_state, initial_allocation
from ..core.energy import rate as _rate
from ..core.sp1 import (_OUTER_ITERS, _coeffs, _f_of_lambda_diff,
                        _lambda_of_T, _s_of_lambda_diff, _sp1_bounds,
                        round_resolution, sp1_stationarity)
from ..core.sp2 import (G, _b_min, _clamp_rmin, _denergy2_dB2, _denergy_dB,
                        _p_rate, _sp2_direct_impl, r_min, sp2_stationarity)
from ..core.types import (_SYS_ARRAYS, _SYS_SCALARS, Allocation, SystemParams,
                          Weights)
from ..kernels.ops import kernel_mode

Array = jnp.ndarray

#: SystemParams leaves differentiated by default (ISSUE 10 contract).
DEFAULT_WRT = ("gain", "cycles", "bandwidth_total", "kappa")

#: Metric order in the stacked output / gradient rows.
METRICS = ("objective", "energy", "time", "accuracy")


def _stop_tree(tree):
    return jax.tree_util.tree_map(lax.stop_gradient, tree)


# ---------------------------------------------------------------------------
# differentiable one-step map Phi (SP1 + SP2 with IFT-corrected inner solves)
# ---------------------------------------------------------------------------

def _sp1_diff(sys: SystemParams, warr: Array, acc: AccuracyModel, tt: Array):
    """Differentiable replica of `core.sp1._solve_sp1_impl`.

    The nested T/lambda bisection runs under stop_gradient (bit-compatible
    with the forward "bisect" engine); the KKT point (lam, T) then gets one
    arrowhead Newton step on the traced `sp1_stationarity` residuals, which
    restores the exact implicit derivative of the dual water-filling system

        M_n(lam_n) = T   (lam_n > 0),      sum_n lam_n = w2 Rg.
    """
    sg = lax.stop_gradient
    # mirror bcd's warr_sp1 clamp (w2 > 0 keeps the dual target positive)
    w = Weights(warr[0], jnp.maximum(warr[1], 1e-9), warr[2])
    sys0 = _stop_tree(sys)
    w0 = Weights(sg(w.w1), sg(w.w2), sg(w.rho))
    tt0 = sg(tt)

    _, q0 = _coeffs(sys0, w0)
    lam_hi, target0, T_lo, T_hi = _sp1_bounds(sys0, w0, q0, tt0)

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        lam = _lambda_of_T(sys0, w0, acc, mid, tt0, lam_hi)
        more_time = jnp.sum(lam) > target0
        return jnp.where(more_time, mid, lo), jnp.where(more_time, hi, mid)

    lo, hi = lax.fori_loop(0, _OUTER_ITERS, body, (T_lo, T_hi))
    T0 = 0.5 * (lo + hi)
    lam0 = _lambda_of_T(sys0, w0, acc, T0, tt0, lam_hi)

    # SP1 active set: fast lanes snap lam = 0 (complementary slackness) and
    # padded lanes are inactive by construction. Both must be masked OUT of
    # every traced recomputation: _f_of_lambda's cbrt has an infinite
    # derivative at lam = 0 and would turn even zero cotangents into NaN.
    eff = lam0 > 0.0
    if sys.active is not None:
        eff = eff & sys.active

    # traced residuals at the stop-grad KKT point ...
    r_n, r_sum = sp1_stationarity(sys, w, acc, lam0, T0, tt, mask=eff)
    # ... and the per-device makespan slope M'_n < 0 (diagonal jvp at the
    # stop-grad point; the corrected closed forms inside sp1_stationarity
    # carry the true derivative where the raw bisections would carry zero)
    def mk(lam):
        return sp1_stationarity(sys0, w0, acc, lam, T0, tt0, mask=eff)[0]

    _, dM = jax.jvp(mk, (lam0,), (jnp.ones_like(lam0),))

    # devices holding the makespan-equalization constraint with a
    # responsive slope get the arrowhead correction; the rest keep lam = 0
    act = eff & (dM < -1e-30)
    inv = jnp.where(act, 1.0 / jnp.where(act, dM, -1.0), 0.0)
    denom = jnp.sum(inv)
    ok = jnp.abs(denom) > 1e-30
    # arrowhead solve of the linearized system:
    #   M'_n dlam_n - dT = -r_n  (active n),   sum dlam = -r_sum
    dT = jnp.where(ok,
                   (jnp.sum(jnp.where(act, r_n, 0.0) * inv) - r_sum)
                   / jnp.where(ok, denom, 1.0),
                   jnp.zeros_like(T0))
    dlam = jnp.where(act, (dT - r_n) * inv, 0.0)
    lam = lam0 + dlam
    T = T0 + dT

    # guarded primal recovery: active lanes track the smooth closed forms,
    # lam = 0 lanes hold the one-sided f = f_min (matching the forward's
    # clip(cbrt(0))) and keep s*'s genuine smooth dependence through psi
    lam_s = jnp.where(eff, lam, jnp.ones_like(lam))
    f = _f_of_lambda_diff(sys, w, lam_s)
    f = jnp.where(eff, f, jnp.asarray(sys.f_min, f.dtype))
    s_hat = _s_of_lambda_diff(sys, w, acc, lam, f=f)
    # discrete snap: piecewise-constant in theta -> stop-grad (zero a.e.)
    s_disc = round_resolution(sys0, sg(s_hat))
    _, q = _coeffs(sys, w)
    T_out = jnp.max(q * s_disc ** 2 / jnp.maximum(f, 1e-9) + tt)
    return f, s_disc, s_hat, jnp.maximum(T, T_out)


def _sp2_diff(sys: SystemParams, rmin: Array) -> Tuple[Array, Array]:
    """Differentiable replica of `core.sp2._sp2_direct_impl`.

    The forward solve runs under stop_gradient and the replica is built
    AROUND its output B0, so the replica equals the forward bit-for-bit at
    the linearization point (crucial: the adjoint solve amplifies any
    base-point inconsistency along the budget-coupling direction). Traced
    structure, lane by lane at the frozen solved point:

    * rate-floor lanes (B0 = b_min, the p_max kink where the clipped and
      rate branches of E_n meet): B tracks the traced root of
      G(p_max, b) = rmin (stop-grad bisection + one Newton step);
    * fit-floor lanes (B0 at the scaled floor b_lo = fit * b_min): B tracks
      the traced floor;
    * every other lane: B tracks the root of dE_n/dB + mu_n = 0 via one
      Newton step at the frozen branch. The per-lane multiplier is
      mu_n = c_n * mu_hi with c_n frozen: the forward's carried-bracket
      search collapses each lane at the budget-bisection step where its
      Newton iterate converged, so lanes hold slightly DIFFERENT effective
      multipliers — all dyadic fractions c_n of the traced bracket ceiling
      mu_hi(theta) = 1.001 * max_n -E_n'(b_lo) (the fraction is a.e.
      locally constant, the ceiling carries the true sensitivity).

    Finally the forward's exact-budget projection is applied in delta form:
    the traced budget violation is redistributed over the lanes'
    frozen surplus shares, B += (B_total - sum B) * sg(surplus / sum
    surplus). This keeps sum B = B_total as a traced identity (the forward
    enforces it to machine precision every step) without the forward
    expression's division by the tiny traced surplus mass, which would
    amplify base-point noise ~1000x.
    """
    sg = lax.stop_gradient
    sys0 = _stop_tree(sys)
    rmin_c = _clamp_rmin(sys, rmin)
    rmin0 = sg(rmin_c)

    _, B0, _ = _sp2_direct_impl(sys0, sg(rmin), True, True)
    dtype = B0.dtype

    # differentiable rate floor b_min: Newton-correct the stop-grad
    # bisection root of G(p_max, b) = rmin
    b0 = _b_min(sys0, rmin0)
    t = sys0.gain * sys0.p_max / (sys0.noise_psd * jnp.maximum(b0, 1e-12))
    GB = jnp.maximum((jnp.log1p(t) - t / (1.0 + t)) / jnp.log(2.0), 1e-30)
    pmax_b = jnp.broadcast_to(jnp.asarray(sys.p_max, dtype), B0.shape)
    b_min = b0 - (G(sys, pmax_b, b0) - rmin_c) / GB
    active = sys.active if sys.active is not None \
        else jnp.full(B0.shape, True)
    b_min = jnp.where(active, b_min, jnp.zeros((), dtype))
    b_min0 = sg(b_min)
    # ... then replicate the forward's best-effort fit scaling for the box
    fit = jnp.minimum(1.0, 0.999 * sys.bandwidth_total
                      / jnp.maximum(jnp.sum(b_min), 1e-30))
    b_lo = b_min * fit
    b_lo0 = sg(b_lo)

    # frozen lane classification at the solved point (module docstring)
    atkink = active & (jnp.abs(B0 - b_min0) <= 1e-6 * jnp.maximum(b_min0,
                                                                  1e-30))
    atfloor = active & ~atkink & (B0 <= b_lo0 * (1.0 + 1e-6))
    interior = active & ~atkink & ~atfloor

    # per-lane effective multiplier mu_n = c_n * mu_hi (docstring): the
    # frozen fraction comes from the forward's own slope at B0, the traced
    # ceiling from the forward's mu_hi sizing rule
    neg_slope = -_denergy_dB(sys, rmin_c, b_lo)
    neg_slope = jnp.where(active, neg_slope, jnp.zeros((), dtype))
    mu_hi = jnp.maximum(jnp.max(neg_slope), 1e-30) * (1.0 + 1e-3)
    mu_lane0 = jnp.maximum(-_denergy_dB(sys0, rmin0, B0), 0.0)
    mu_eff = sg(mu_lane0 / sg(mu_hi)) * mu_hi

    # one Newton step of root tracking on the frozen smooth branch:
    # g_n = dE/dB(B0) + mu_eff is exactly zero at the base point
    g_n = _denergy_dB(sys, rmin_c, B0) + mu_eff
    E2 = jnp.maximum(sg(_denergy2_dB2(sys0, rmin0, B0)),
                     jnp.finfo(dtype).tiny)
    B_int = B0 - g_n / E2
    B = jnp.where(atkink, b_min,
                  jnp.where(atfloor, b_lo,
                            jnp.where(interior, B_int,
                                      jnp.zeros((), dtype))))
    # exact-budget projection, delta form with frozen surplus shares
    surplus0 = jnp.where(active, jnp.maximum(sg(B0) - b_lo0, 0.0),
                         jnp.zeros((), dtype))
    wgt = surplus0 / jnp.maximum(jnp.sum(surplus0), 1e-30)
    B = B + wgt * (sys.bandwidth_total - jnp.sum(B))
    B = jnp.where(active, B, jnp.zeros((), dtype))
    p = jnp.clip(_p_rate(sys, rmin_c, B), sys.p_min, sys.p_max)
    return B, p


def _phi_step(x, sys: SystemParams, warr: Array, acc: AccuracyModel):
    """One differentiable BCD step (mirrors `bcd._allocate_impl`'s `step`).

    Returns the next (B, p) plus the SP1 side outputs (f, s, s_hat, T)."""
    B, p = x
    tt = sys.bits / jnp.maximum(_rate(sys, B, p), 1e-12)
    f, s_disc, s_hat, T = _sp1_diff(sys, warr, acc, tt)
    rmin = r_min(sys, f, s_disc, T)
    B2, p2 = _sp2_diff(sys, rmin)
    return (B2, p2), (f, s_disc, s_hat, T)


def _step_metrics(x, sys: SystemParams, warr: Array, acc: AccuracyModel):
    """Stacked (objective, energy, time, accuracy) + the realized Allocation,
    evaluated through one differentiable BCD step at the fixed point."""
    (B2, p2), (f, s_disc, s_hat, T) = _phi_step(x, sys, warr, acc)
    alloc = Allocation(bandwidth=B2, power=p2, freq=f, resolution=s_disc,
                       s_relaxed=s_hat, T=T)
    E = en.total_energy(sys, alloc)
    Tt = en.total_time(sys, alloc)
    A = en.total_accuracy(acc, alloc, sys.active)
    obj = warr[0] * E + warr[1] * Tt - warr[2] * A
    return jnp.stack([obj, E, Tt, A]), alloc


# ---------------------------------------------------------------------------
# the custom_vjp fixed point + the jitted grad program
# ---------------------------------------------------------------------------

def _normalize_weights(wr: Array) -> Array:
    # same contract as `api.problem.weights_leaf` / `Weights.normalized()`:
    # every component divides by w1 + w2 (rho included)
    return wr / (wr[0] + wr[1])


def _cell_grad(sysc: SystemParams, lv, wr, initc, acc, spec: SolverSpec,
               wrt, adjoint_iters: int, kernel: str):
    """Metrics + per-metric gradients for one cell. `lv` duplicates the
    `wrt` leaves of `sysc` as the differentiated operands."""
    alloc0 = initc if initc is not None else initial_allocation(sysc)
    state0 = _init_carry_state(sysc, alloc0)

    def build(lv_):
        return sysc.replace(**dict(zip(wrt, lv_)))

    @jax.custom_vjp
    def fp(lv_, warr):
        sys = build(lv_)
        out = _allocate_impl(sys, warr, acc, state0, spec.max_iters,
                             spec.tol, spec.sp1_method, spec.sp2_method,
                             spec.sp2_iters, kernel)
        return out[0], out[1]

    def fwd(lv_, warr):
        x = fp(lv_, warr)
        return x, (x, lv_, warr)

    def bwd(res, v):
        x, lv_, warr = res

        def phi(xx, l_, w_):
            return _phi_step(xx, build(l_), w_, acc)[0]

        _, pull = jax.vjp(phi, x, lv_, warr)
        if adjoint_iters > 0:
            # Neumann adjoint: u = sum_k (Phi_x^T)^k v solves u = v + Phi_x^T u
            u = lax.fori_loop(
                0, adjoint_iters,
                lambda _, u_: jax.tree_util.tree_map(jnp.add, v, pull(u_)[0]),
                v)
        else:
            # exact adjoint: the state is only (B, p) — 2N unknowns — so we
            # materialize Phi_x by jacrev and solve (I - Phi_x^T) u = v
            # directly. The budget-coupling direction puts an eigenvalue of
            # Phi_x near 1, which stalls the Neumann series but is perfectly
            # well-posed for a dense solve.
            flat_x, unravel = ravel_pytree(x)

            def phi_flat(xf):
                return ravel_pytree(phi(unravel(xf), lv_, warr))[0]

            J = jax.jacrev(phi_flat)(flat_x)
            vf, _ = ravel_pytree(v)
            eye = jnp.eye(flat_x.size, dtype=flat_x.dtype)
            u = unravel(jnp.linalg.solve(eye - J.T, vf))
        _, d_lv, d_wr = pull(u)
        return d_lv, d_wr

    fp.defvjp(fwd, bwd)

    def m(lv_, wr_):
        warr = _normalize_weights(wr_)
        x = fp(lv_, warr)
        return _step_metrics(x, build(lv_), warr, acc)

    mvec, vjp_fun, alloc = jax.vjp(m, lv, wr, has_aux=True)
    eye = jnp.eye(len(METRICS), dtype=mvec.dtype)
    d_lv, d_wr = jax.vmap(vjp_fun)(eye)   # one linearization, 4 cotangents
    return mvec, d_lv, d_wr, alloc


@partial(jax.jit,
         static_argnames=("acc", "spec", "wrt", "adjoint_iters", "fleet",
                          "kernel"))
def _solve_and_grad_impl(sysp, leaf_vals, warr_raw, init, acc, spec, wrt,
                         adjoint_iters, fleet, kernel):
    def cell(sysc, lv, wr, initc):
        return _cell_grad(sysc, lv, wr, initc, acc, spec, wrt, adjoint_iters,
                          kernel)

    if fleet:
        return jax.vmap(cell)(sysp, leaf_vals, warr_raw, init)
    return cell(sysp, leaf_vals, warr_raw, init)


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GradResult:
    """Value + gradients of the realized allocation metrics.

    value : dict metric -> scalar (single cell) or (C,) array (fleet) for
        each of `METRICS` = (objective, energy, time, accuracy).
    grads : dict metric -> {"weights": (3,)/(C, 3) gradient w.r.t. the RAW
        (w1, w2, rho) vector (the normalization Jacobian is included), plus
        one entry per `wrt` leaf with that leaf's shape}.
    allocation : the realized `Allocation` (per-cell arrays under a fleet).
    wrt : the SystemParams leaf names differentiated.
    """
    value: Dict[str, Array]
    grads: Dict[str, Dict[str, Array]]
    allocation: Allocation
    wrt: Tuple[str, ...]


def _raw_weights(w, dtype, cells: Optional[int]) -> Array:
    """Raw (UNnormalized) (3,)/(C, 3) weight operand — gradients are taken
    w.r.t. these entries, with the w1+w2 normalization inside the program."""
    if isinstance(w, Weights):
        arr = jnp.stack([jnp.asarray(w.w1, dtype), jnp.asarray(w.w2, dtype),
                         jnp.asarray(w.rho, dtype)], axis=-1)
    elif isinstance(w, (list, tuple)) and w and isinstance(w[0], Weights):
        arr = jnp.asarray([[wc.w1, wc.w2, wc.rho] for wc in w], dtype)
    else:
        arr = jnp.asarray(w, dtype)
    if arr.ndim == 0 or arr.shape[-1] != 3 or arr.ndim > 2:
        raise ValueError(
            f"solve_and_grad: weights must lower to (3,) or (C, 3), got "
            f"shape {jnp.shape(arr)}")
    if cells is None:
        if arr.ndim != 1:
            raise ValueError(
                "solve_and_grad: single-cell problem, but weights have a "
                f"cell axis ({arr.shape})")
        return arr
    if arr.ndim == 1:
        arr = jnp.broadcast_to(arr, (cells, 3))
    if arr.shape[0] != cells:
        raise ValueError(
            f"solve_and_grad: {arr.shape[0]} weight rows for {cells} cells")
    return arr


def _take_metric(x, i: int, fleet: bool):
    return x[:, i] if fleet else x[i]


def solve_and_grad(problem: Problem, spec: Optional[SolverSpec] = None, *,
                   wrt: Tuple[str, ...] = DEFAULT_WRT,
                   adjoint_iters: int = 30) -> GradResult:
    """Solve the allocation problem AND differentiate the realized metrics.

    Returns the (objective, energy, time, accuracy) of the BCD fixed point
    together with their gradients w.r.t. the raw weight vector and the
    requested `SystemParams` leaves, computed by implicit differentiation
    of the KKT conditions (module docstring). Composes with per-cell weight
    batches: a stacked (C, N) system with (C, 3) weights differentiates in
    ONE compiled program (the same vmap plumbing as `solve`).

    Parameters
    ----------
    problem : a plain BCD `Problem` (no mesh / rounds / deadline / assoc).
    spec : `SolverSpec` for the forward solve. For finite-difference-grade
        smoothness use `sp1_method="bisect"` with a tight `tol` in f64 —
        the backward pass linearizes the bisect engine's KKT point.
    wrt : SystemParams leaf names to differentiate (float leaves only).
    adjoint_iters : number of matrix-free Neumann iterations for the
        adjoint fixed point (error decays like the BCD contraction factor
        to this power on the contractive subspace); 0 switches to an exact
        dense solve of the 2N-dim adjoint system. The Neumann default is
        deliberately truncated: at saturated fixed points (module
        docstring) the exact resolvent amplifies the neutral modes where
        the one-step linearization is least trustworthy.

    Notes
    -----
    `accuracy` responds to theta only through the discrete resolution menu,
    so its gradient is the a.e.-correct zero subgradient almost everywhere
    (the relaxed s-hat is exposed via `result.allocation.s_relaxed`).
    """
    spec = SolverSpec() if spec is None else spec
    if problem.mesh is not None or problem.rounds is not None \
            or problem.deadline is not None or problem.assoc is not None:
        raise ValueError(
            "solve_and_grad: only plain BCD problems are differentiable "
            "(mesh/rounds/deadline/assoc topologies are not)")
    for name in wrt:
        if name not in _SYS_SCALARS + _SYS_ARRAYS:
            raise ValueError(
                f"solve_and_grad: unknown SystemParams leaf {name!r}; "
                f"differentiable leaves are {_SYS_SCALARS + _SYS_ARRAYS}")
    wrt = tuple(wrt)

    from ..api.solve import _apply_dtype   # local: avoid import cycle
    sysp, init = _apply_dtype(problem.system, problem.init, spec.dtype)
    acc = problem.acc if problem.acc is not None else default_accuracy()
    cells = problem.cells
    dtype = jnp.asarray(sysp.gain).dtype
    leaf_vals = tuple(jnp.asarray(getattr(sysp, k), dtype) for k in wrt)
    warr_raw = _raw_weights(problem.weights, dtype, cells)

    mvec, d_lv, d_wr, alloc = _solve_and_grad_impl(
        sysp, leaf_vals, warr_raw, init, acc, spec, wrt,
        int(adjoint_iters), cells is not None, kernel_mode())

    fleet = cells is not None
    value = {m: _take_metric(mvec, i, fleet) for i, m in enumerate(METRICS)}
    grads = {}
    for i, m in enumerate(METRICS):
        g = {"weights": _take_metric(d_wr, i, fleet)}
        for k, name in enumerate(wrt):
            g[name] = _take_metric(d_lv[k], i, fleet)
        grads[m] = g
    return GradResult(value=value, grads=grads, allocation=alloc, wrt=wrt)
