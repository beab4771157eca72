"""Subproblem 1 (paper §V-A, Appendix B): optimize (f, s, T) given (p, B).

    min_{f, s_hat, T}  w1 Rg sum_n alpha_n s_hat^2 f^2 + w2 Rg T - rho sum_n A_n(s_hat)
    s.t. f in [fmin, fmax], s_hat in [s_lo, s_hi],
         q_n s_hat^2 / f + T_trans_n <= T

KKT structure (paper eqs. A.2-A.7):
    f_n*(lambda)     = cbrt(lambda_n / (2 w1 Rg kappa))            clipped to box
    s_hat_n*(lambda) solves  s * (2 a_n f^2 + 2 lambda q_n / f) = rho A_n'(s)
    sum_n lambda_n   = w2 Rg

Instead of CVX on the dual (A.8) we solve the KKT system exactly by
water-filling on the scalar map T -> Sigma_n lambda_n(T), where lambda_n(T)
inverts the strictly decreasing per-device makespan T_n(lambda) (A.4/A.6
with the box clips folded in) and the outer root Sigma_n lambda_n(T) = w2 Rg
enforces the dual feasibility condition A.7. Two engines share that
formulation:

  * method="sweep" (default): a batched T-grid sweep — every round evaluates
    Sigma_n lambda_n(T) for a whole grid of candidate deadlines in one
    device pass through `kernels.ops.sp1_lambda_sum` (in the kernel mode the
    caller resolved outside jit — see `kernels.ops.kernel_mode`) and
    re-grids geometrically inside the sign-change bracket, finishing with
    secant interpolation. For the paper's LinearAccuracy the inner
    inversion lambda_n(T) is CLOSED FORM (the clipping regimes of A.2/A.3
    each invert exactly — see `kernels.sp1_sweep.lambda_of_T_linear`), so
    one sweep costs O(grid) per device instead of O(outer x inner)
    bisection steps; generic concave accuracy models run the same sweep
    with a vmapped per-grid-point bisection for lambda_n(T).
  * method="bisect": the original nested bisection (inner lambda, outer T),
    kept bit-stable as the parity oracle for the sweep.

This supports any concave accuracy model A_n, not just the paper's linear
special case (DESIGN.md §5). Fully jitted (lax.fori_loop bisections).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .accuracy import AccuracyModel, LinearAccuracy
from .types import SystemParams, Weights

Array = jnp.ndarray

_INNER_ITERS = 56
_OUTER_ITERS = 56
_S_ITERS = 48

# T-grid sweep shape: `_SWEEP_ROUNDS` rounds of `_SWEEP_POINTS`-point grids
# shrink the bracket by (points-1)^rounds; 3 x 16 resolves the ~18-nat
# default [T_lo, T_hi] range to ~5e-3 relative before the secant step
# (the objective is stationary in T at the root, so that is ~1e-8 relative
# on the objective — see the parity tests).
_SWEEP_POINTS = 16
_SWEEP_ROUNDS = 3
# generic (non-linear) accuracy models pay a full lambda-bisection per grid
# point, so sweep a coarser grid over one extra round — same total bracket
# reduction (11^4 > 15^3) at 48 instead of 64 bisection-backed evaluations
_SWEEP_POINTS_GENERIC = 12
_SWEEP_ROUNDS_GENERIC = 4


def _coeffs(sys: SystemParams, w: Weights):
    """alpha_n (energy coeff, incl. w1 Rg) and q_n (cycles per s^2)."""
    q = sys.local_iters * sys.zeta * sys.cycles * sys.samples
    alpha = w.w1 * sys.global_rounds * sys.kappa * q
    return alpha, q


def _f_of_lambda(sys: SystemParams, w: Weights, lam: Array) -> Array:
    # dtype-aware guard: 1e-300 underflows to 0 in f32, and w1 == 0 (pure
    # latency weighting) would make this cbrt(0/0) = NaN at lam = 0
    tiny = jnp.finfo(jnp.asarray(lam).dtype).tiny
    f_unc = jnp.cbrt(lam / jnp.maximum(
        2.0 * w.w1 * sys.global_rounds * sys.kappa, tiny))
    return jnp.clip(f_unc, sys.f_min, sys.f_max)


def _f_of_lambda_diff(sys: SystemParams, w: Weights, lam: Array) -> Array:
    """Value-identical (to 1 ulp) to `_f_of_lambda`, gradient-safe.

    The fused form cbrt(lam / denom) backpropagates -lam / denom^2, and
    denom = 2 w1 Rg kappa ~ 1e-27 underflows f32 when squared — every
    kappa/w1 cotangent becomes inf. Splitting the cbrt keeps the vjp on
    the cbrt scale (denom^(4/3) ~ 1e-36, representable), so the diff path
    (`sp1_stationarity`, `repro.diff`) uses this variant."""
    tiny = jnp.finfo(jnp.asarray(lam).dtype).tiny
    denom = jnp.maximum(2.0 * w.w1 * sys.global_rounds * sys.kappa, tiny)
    f_unc = jnp.cbrt(lam) / jnp.cbrt(denom)
    return jnp.clip(f_unc, sys.f_min, sys.f_max)


def _s_of_lambda(sys: SystemParams, w: Weights, acc: AccuracyModel, lam: Array) -> Array:
    """Solve s*(2 a f^2 + 2 lam q / f) = rho A'(s) on [s_lo, s_hi]."""
    alpha, q = _coeffs(sys, w)
    f = _f_of_lambda(sys, w, lam)
    psi = 2.0 * alpha * f ** 2 + 2.0 * lam * q / jnp.maximum(f, 1e-9)

    if isinstance(acc, LinearAccuracy):
        s_unc = w.rho * acc.slope / jnp.maximum(
            psi, jnp.finfo(jnp.asarray(psi).dtype).tiny)
        return jnp.clip(s_unc, sys.s_lo, sys.s_hi)

    def h(s):  # increasing in s (A concave)
        return s * psi - w.rho * acc.deriv(s)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        pos = h(mid) > 0
        return jnp.where(pos, lo, mid), jnp.where(pos, mid, hi)

    lo0 = jnp.full_like(lam, sys.s_lo)
    hi0 = jnp.full_like(lam, sys.s_hi)
    lo, hi = lax.fori_loop(0, _S_ITERS, body, (lo0, hi0))
    s = 0.5 * (lo + hi)
    s = jnp.where(h(lo0) >= 0, sys.s_lo, s)
    s = jnp.where(h(hi0) <= 0, sys.s_hi, s)
    return s


def _makespan_of_lambda(sys: SystemParams, w: Weights, acc: AccuracyModel,
                        lam: Array, tt: Array) -> Array:
    _, q = _coeffs(sys, w)
    f = _f_of_lambda(sys, w, lam)
    s = _s_of_lambda(sys, w, acc, lam)
    return q * s ** 2 / jnp.maximum(f, 1e-9) + tt


def _s_of_lambda_diff(sys: SystemParams, w: Weights, acc: AccuracyModel,
                      lam: Array, f: Array | None = None) -> Array:
    """Differentiable s*(lambda).

    For `LinearAccuracy` the closed form in `_s_of_lambda` is already smooth,
    so it is returned as-is. For generic accuracy models the fixed-iteration
    bisection has zero derivative, so the root is re-expressed as one Newton
    correction of the stop-gradient bisection solution: equal in value to
    solver precision, with the exact implicit-function-theorem derivative.
    Lanes clipped at the static [s_lo, s_hi] box keep the (constant) bound.

    `f` optionally supplies a precomputed (possibly lane-guarded) CPU
    frequency; callers that must avoid `_f_of_lambda`'s cbrt at lam = 0
    (infinite derivative) pass the guarded value — see `sp1_stationarity`.
    """
    alpha, q = _coeffs(sys, w)
    if f is None:
        f = _f_of_lambda_diff(sys, w, lam)
    psi = 2.0 * alpha * f ** 2 + 2.0 * lam * q / jnp.maximum(f, 1e-9)

    if isinstance(acc, LinearAccuracy):
        # floor at sqrt(tiny), not tiny: the division's vjp squares the
        # denominator, and tiny**2 underflows to 0 — a zero-coefficient
        # (padded) lane with psi = 0 would then emit 0 * inf = NaN through
        # the clip. Any psi below sqrt(tiny) clips to s_hi either way, so
        # the primal matches `_s_of_lambda` bit-for-bit.
        dt = jnp.asarray(psi).dtype
        s_unc = w.rho * acc.slope / jnp.maximum(
            psi, jnp.sqrt(jnp.finfo(dt).tiny))
        return jnp.clip(s_unc, sys.s_lo, sys.s_hi)

    s0 = lax.stop_gradient(_s_of_lambda(sys, w, acc, lam))
    h = s0 * psi - w.rho * acc.deriv(s0)          # traced residual at s0
    # h'(s) = psi - rho A''(s) > 0 (A concave), evaluated under stop-grad;
    # A'' per-element via a diagonal jvp of acc.deriv
    _, d2A = jax.jvp(acc.deriv, (s0,), (jnp.ones_like(s0),))
    hp = lax.stop_gradient(psi) - w.rho * lax.stop_gradient(d2A)
    hp = jnp.maximum(hp, jnp.finfo(s0.dtype).tiny)
    eps = 1e-9
    interior = (s0 > sys.s_lo * (1.0 + eps)) & (s0 < sys.s_hi * (1.0 - eps))
    return jnp.where(interior, s0 - h / hp, s0)


def sp1_stationarity(sys: SystemParams, w: Weights, acc: AccuracyModel,
                     lam: Array, T: Array, tt: Array, mask: Array | None = None):
    """SP1 KKT residuals at a candidate dual point (lam, T).

    Returns `(r_n, r_sum)` where `r_n = M_n(lam_n) - T` (per-device makespan
    equalization, meaningful on the active set lam_n > 0) and
    `r_sum = sum_n lam_n - w2 Rg` (dual budget, eq. (18)). Both residuals are
    differentiable in (lam, T, tt), the `SystemParams` leaves, and the
    weights — the resolution subproblem inside M_n goes through
    `_s_of_lambda_diff`. Exported for `repro.diff.implicit`, which corrects
    the stop-gradient bisection solve with one arrowhead Newton step on
    exactly these residuals.

    `mask` (optional boolean per-device) restricts the traced system to the
    SP1 active set: lanes outside it — lam_n = 0 fast lanes and padded
    inactive lanes — hold f = f_min with zero one-sided derivative, carry
    r_n = 0, and drop out of the dual budget sum. Required whenever any
    lam_n = 0: `_f_of_lambda`'s cbrt has an infinite derivative at 0, and
    even a zero cotangent times that is NaN.
    """
    _, q = _coeffs(sys, w)
    if mask is None:
        f = _f_of_lambda_diff(sys, w, lam)
        s = _s_of_lambda_diff(sys, w, acc, lam, f=f)
        r_n = q * s ** 2 / jnp.maximum(f, 1e-9) + tt - T
        r_sum = jnp.sum(lam) - w.w2 * sys.global_rounds
        return r_n, r_sum
    lam_s = jnp.where(mask, lam, jnp.ones_like(lam))
    f = _f_of_lambda_diff(sys, w, lam_s)
    f = jnp.where(mask, f, jnp.asarray(sys.f_min, f.dtype))
    s = _s_of_lambda_diff(sys, w, acc, lam_s, f=f)
    r_n = jnp.where(mask, q * s ** 2 / jnp.maximum(f, 1e-9) + tt - T,
                    jnp.zeros_like(lam))
    r_sum = jnp.sum(jnp.where(mask, lam, jnp.zeros_like(lam))) \
        - w.w2 * sys.global_rounds
    return r_n, r_sum


def _lambda_of_T(sys: SystemParams, w: Weights, acc: AccuracyModel,
                 T: Array, tt: Array, lam_hi: float) -> Array:
    """Per-device inverse of the decreasing map lambda -> T_n(lambda)."""
    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        too_slow = _makespan_of_lambda(sys, w, acc, mid, tt) > T
        return jnp.where(too_slow, mid, lo), jnp.where(too_slow, hi, mid)

    lo0 = jnp.zeros_like(tt)
    hi0 = jnp.full_like(tt, lam_hi)
    lo, hi = lax.fori_loop(0, _INNER_ITERS, body, (lo0, hi0))
    lam = 0.5 * (lo + hi)
    fast = _makespan_of_lambda(sys, w, acc, jnp.zeros_like(tt), tt) <= T
    return jnp.where(fast, 0.0, lam)


def round_resolution(sys: SystemParams, s_hat: Array) -> Array:
    """Discrete mapping of eq. (20): nearest resolution by midpoint thresholds."""
    # pin the static menu to the solve dtype: an f64 menu would silently
    # promote s (and everything downstream, incl. the BCD while_loop carry)
    # out of an f32 system's dtype
    res = jnp.asarray(sys.resolutions, s_hat.dtype)
    idx = jnp.argmin(jnp.abs(s_hat[:, None] - res[None, :]), axis=1)
    return res[idx]


def _sp1_bounds(sys: SystemParams, w: Weights, q: Array, tt: Array):
    """(lam_hi, target, T_lo, T_hi) shared by both SP1 engines."""
    lam_hi = jnp.maximum(jnp.maximum(
        2.0 * w.w1 * sys.global_rounds * sys.kappa * sys.f_max ** 3,
        w.w2 * sys.global_rounds), 1.0) * 1e4
    target = w.w2 * sys.global_rounds
    T_lo = jnp.max(q * sys.s_lo ** 2 / sys.f_max + tt) * (1.0 + 1e-12)
    T_hi = jnp.max(q * sys.s_hi ** 2 / jnp.maximum(sys.f_min, 1e-3) + tt) * 2.0
    return lam_hi, target, T_lo, jnp.asarray(T_hi, T_lo.dtype)


def _finish_sp1(sys: SystemParams, w: Weights, acc: AccuracyModel,
                q: Array, lam: Array, tt: Array, T: Array):
    f = _f_of_lambda(sys, w, lam)                      # eq. (19)
    s_hat = _s_of_lambda(sys, w, acc, lam)
    s = round_resolution(sys, s_hat)                   # eq. (20)
    # makespan consistent with the discrete s (feeds SP2's r_min)
    T_out = jnp.max(q * s ** 2 / jnp.maximum(f, 1e-9) + tt)
    return f, s, s_hat, jnp.maximum(T, T_out)


@partial(jax.jit, static_argnames=("acc",))
def _solve_sp1_impl(sys: SystemParams, warr: Array, acc: AccuracyModel,
                    tt: Array):
    """Nested-bisection engine (method="bisect") — the sweep's parity oracle."""
    w = Weights(warr[0], warr[1], warr[2])
    _, q = _coeffs(sys, w)
    lam_hi, target, T_lo, T_hi = _sp1_bounds(sys, w, q, tt)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        lam = _lambda_of_T(sys, w, acc, mid, tt, lam_hi)
        more_time = jnp.sum(lam) > target      # lambda too large -> raise T
        return jnp.where(more_time, mid, lo), jnp.where(more_time, hi, mid)

    lo, hi = lax.fori_loop(0, _OUTER_ITERS, body, (T_lo, T_hi))
    T = 0.5 * (lo + hi)
    lam = _lambda_of_T(sys, w, acc, T, tt, lam_hi)
    return _finish_sp1(sys, w, acc, q, lam, tt, T)


@partial(jax.jit, static_argnames=("acc", "kernel"))
def _solve_sp1_sweep_impl(sys: SystemParams, warr: Array, acc: AccuracyModel,
                          tt: Array, kernel: str):
    """Batched T-grid sweep engine (method="sweep", the default).

    Each round evaluates Sigma_n lambda_n(T) for a whole geometric grid of
    candidate deadlines in one pass (`kernels.ops.sp1_lambda_sum` for
    LinearAccuracy, a vmapped lambda-bisection otherwise), narrows to the
    sign-change bracket of Sigma lambda - w2 Rg, and finishes with a secant
    step — replacing `_OUTER_ITERS` sequential outer bisections. `kernel`
    is the resolved mode of the sweep kernel (`kernels.ops.KERNEL_MODES`)."""
    from ..kernels import ops as kops
    from ..kernels.sp1_sweep import N_CONSTS, lambda_of_T_linear

    w = Weights(warr[0], warr[1], warr[2])
    _, q = _coeffs(sys, w)
    lam_hi, target, T_lo, T_hi = _sp1_bounds(sys, w, q, tt)
    dtype = T_lo.dtype
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)

    linear = isinstance(acc, LinearAccuracy)
    if linear:
        k3 = 2.0 * w.w1 * sys.global_rounds * sys.kappa
        consts = jnp.zeros((N_CONSTS,), dtype).at[:7].set(jnp.stack([
            jnp.asarray(c, dtype) for c in
            (k3, w.rho * acc.slope, sys.f_min, sys.f_max,
             sys.s_lo, sys.s_hi, lam_hi)]))

        def lam_sum(grid):
            return kops.sp1_lambda_sum(grid, q, tt, consts,
                                       impl=kernel).astype(dtype)

        n_grid, rounds = _SWEEP_POINTS, _SWEEP_ROUNDS
    else:
        def lam_sum(grid):
            return jax.vmap(lambda Tm: jnp.sum(
                _lambda_of_T(sys, w, acc, Tm, tt, lam_hi)))(grid)

        n_grid, rounds = _SWEEP_POINTS_GENERIC, _SWEEP_ROUNDS_GENERIC

    lo, hi = T_lo, T_hi
    S_lo = S_hi = None
    for _ in range(rounds):
        grid = jnp.geomspace(lo, hi, n_grid).astype(dtype)
        S = lam_sum(grid)
        # Sigma lambda(T) is nonincreasing in T; bracket its target crossing
        under = S < target
        idx = jnp.where(jnp.any(under), jnp.maximum(jnp.argmax(under), 1),
                        n_grid - 1)
        lo, hi = grid[idx - 1], grid[idx]
        S_lo, S_hi = S[idx - 1], S[idx]
    t = jnp.clip((S_lo - target) / jnp.maximum(S_lo - S_hi, tiny), 0.0, 1.0)
    T = lo + t * (hi - lo)

    if linear:
        lam = lambda_of_T_linear(T, q, tt, k3, w.rho * acc.slope,
                                 sys.f_min, sys.f_max, sys.s_lo, sys.s_hi,
                                 lam_hi)
    else:
        lam = _lambda_of_T(sys, w, acc, T, tt, lam_hi)
    return _finish_sp1(sys, w, acc, q, lam, tt, T)


def sp1_engine(method: str, kernel: str):
    """The SP1 solve `(sys, warr, acc, tt) -> (f, s, s_hat, T)` for one BCD
    step: `method` picks the engine, `kernel` is the sweep kernel's mode,
    resolved by `kernels.ops.kernel_mode` outside any jit (the bisection
    runs no kernel and ignores it)."""
    if method == "sweep":
        return partial(_solve_sp1_sweep_impl, kernel=kernel)
    if method == "bisect":
        return _solve_sp1_impl
    raise ValueError(f"method must be sweep|bisect, got {method!r}")


def dual_evals_per_iter(sp1_method: str, acc: AccuracyModel) -> int:
    """SP1 Sigma-lambda(T) dual evaluations one BCD iteration spends,
    counted at the candidate-deadline level (each evaluation inverts
    lambda(T) — closed form for LinearAccuracy under "sweep", an
    `_INNER_ITERS` bisection otherwise). Both engines have fixed trip
    counts and the method/accuracy class are jit static args, so the
    count is exact and known at trace time — `core.bcd` multiplies it by
    the traced iteration count to form the device-resident `sp1_evals`
    counter without adding any compiled work.

    The +1 is the final lambda(T) inversion at the bracketing result
    (the secant T for "sweep", the midpoint for "bisect")."""
    if sp1_method == "sweep":
        if isinstance(acc, LinearAccuracy):
            return _SWEEP_POINTS * _SWEEP_ROUNDS + 1
        return _SWEEP_POINTS_GENERIC * _SWEEP_ROUNDS_GENERIC + 1
    if sp1_method == "bisect":
        return _OUTER_ITERS + 1
    raise ValueError(f"sp1_method must be sweep|bisect, got {sp1_method!r}")


def solve_sp1(sys: SystemParams, w: Weights, acc: AccuracyModel,
              bandwidth: Array, power: Array, method: str = "sweep"
              ) -> Tuple[Array, Array, Array, Array]:
    """Returns (f, s_discrete, s_hat, T).  T is the per-round makespan consistent
    with the rounded resolution (used by SP2 for r_n^min).

    method: "sweep" (batched T-grid dual sweep, the default) or "bisect"
    (the original nested bisection, kept as the parity oracle)."""
    from ..kernels.ops import kernel_mode
    from .energy import rate

    engine = sp1_engine(method, kernel_mode())
    tt = sys.bits / jnp.maximum(rate(sys, bandwidth, power), 1e-12)
    warr = jnp.asarray([w.w1, max(w.w2, 1e-9), w.rho], tt.dtype)
    return engine(sys, warr, acc, tt)


@partial(jax.jit, static_argnames=("acc",))
def _solve_sp1_fixed_impl(sys: SystemParams, warr: Array, acc: AccuracyModel,
                          tt: Array, T_round: Array):
    w = Weights(warr[0], warr[1], warr[2])
    alpha, q = _coeffs(sys, w)
    res = jnp.asarray(sys.resolutions, tt.dtype)            # (M,)
    budget = jnp.maximum(T_round - tt, 1e-9)[:, None]       # (N,1)
    f_req = q[:, None] * res[None, :] ** 2 / budget         # (N,M)
    feas = f_req <= sys.f_max * (1.0 + 1e-9)
    f_opt = jnp.clip(f_req, sys.f_min, sys.f_max)
    obj = alpha[:, None] * res[None, :] ** 2 * f_opt ** 2 - w.rho * acc.value(res)[None, :]
    obj = jnp.where(feas, obj, jnp.inf)
    pick = jnp.argmin(obj, axis=1)
    return f_opt[jnp.arange(tt.shape[0]), pick], res[pick]


def solve_sp1_fixed_T(sys: SystemParams, w: Weights, acc: AccuracyModel,
                      bandwidth: Array, power: Array, T_round: float
                      ) -> Tuple[Array, Array]:
    """Deadline-constrained variant used by the Fig. 8/9 comparisons: the round
    deadline is a hard constraint (no w2*T term). s is discrete with M options,
    so each device is solved *exactly* by enumeration: the smallest feasible
    f (energy rises with f) per option, then argmin over options of
    w1 Rg kappa q s^2 f^2 - rho A(s).  Returns (f, s)."""
    from .energy import rate

    tt = sys.bits / jnp.maximum(rate(sys, bandwidth, power), 1e-12)
    warr = jnp.asarray([w.w1, w.w2, w.rho], tt.dtype)
    return _solve_sp1_fixed_impl(sys, warr, acc, tt, jnp.asarray(T_round, tt.dtype))
