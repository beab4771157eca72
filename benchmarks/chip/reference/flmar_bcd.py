"""Plain reference for the FL-MAR resource allocation (arXiv:2211.08705).

Written from the paper alone and importing nothing of the program under
test. For every cell it minimizes, over bandwidth B, power p, CPU frequency
f and frame resolution s of each device (eq. 12),

    w1 E + w2 T - rho A,
    E = R_g sum_n (p_n t_n + kappa q_n s_n^2 f_n^2),  t_n = d_n / r_n,
    r_n = B_n log2(1 + g_n p_n / (N0 B_n)),            q_n = R_l c_n D_n / s_std^2,
    T = R_g max_n (q_n s_n^2 / f_n + t_n),             A = sum_n A(s_n),

subject to sum_n B_n <= B, p in [p_min, p_max], f in [f_min, f_max] and s on
the resolution menu, by the paper's block-coordinate descent (Algorithm 2)
from its start p = p_max, B = B / N, or, for a re-plan that continues from
an allocation the caller holds, from that allocation's B and p (where the
rate floors fill the budget, SP2 cannot move B, so where the descent stops
depends on where it starts):

  * SP1 (f, s, T given B, p): the KKT system of Appendix B, solved by
    nested bisection: lambda_n(T) inverts the per-device makespan
    q s*(lambda)^2 / f*(lambda) + t_n = T, with f*(lambda) = cbrt(lambda /
    (2 w1 R_g kappa)) and s*(lambda) = rho A' / psi(lambda) clipped to their
    boxes, and T solves sum_n lambda_n(T) = w2 R_g; s is then rounded to the
    nearest menu entry, and T becomes the makespan that rounding gives.
  * SP2 (B, p given f, s, T): minimum transmission energy under the rate
    floors r_n >= d_n / (T - q_n s_n^2 / f_n) (each floor kept below 0.95 of
    the rate at infinite bandwidth), with the power at its boundary value
    clip(p_rate(B), p_min, p_max); the separable convex program in B is
    solved by bisection on the budget multiplier mu, each B_n(mu) by
    bisection on dE_n/dB + mu = 0 (the derivative by automatic
    differentiation). Floors that need the whole budget or more leave no
    choice: B is the floors, scaled down to the budget if they overrun it.

Iterations stop when the relative step of the stacked (B, p, f, s) falls to
the configuration's tolerance (floored at 64 ulps of the dtype), or after
`MAX_ITERS`: the criterion the configuration states, without its cap on
iterations. Every search is a fixed-depth
bisection: slow, and easy to check. `dtype` is the precision of every step
(the control runs this same code one precision lower).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MAX_ITERS = 40
LAM_STEPS = 60       # arithmetic bisection of lambda_n(T) on [0, lam_hi]
T_STEPS = 40         # geometric bisection of T
BMIN_STEPS = 48      # arithmetic bisection of the rate-floor bandwidth
B_STEPS = 48         # arithmetic bisection of B_n(mu)
MU_STEPS = 40        # geometric bisection of mu on [mu_hi 1e-12, mu_hi]


def _cbrt(x):
    return jnp.exp(jnp.log(x) / 3.0)


def _bisect(pred, lo, hi, steps, geometric=False):
    """Shrink [lo, hi] keeping pred(lo) False and pred(hi) True."""
    def body(_, c):
        lo, hi = c
        mid = jnp.sqrt(lo * hi) if geometric else 0.5 * (lo + hi)
        up = pred(mid)
        return jnp.where(up, lo, mid), jnp.where(up, mid, hi)
    return lax.fori_loop(0, steps, body, (lo, hi))


def _cell(g, c, D, d, act, sc, w, B_init, p_init, warm, slope, menu, tol,
          dtype):
    """One cell: per-device (N,) arrays, `sc` the per-cell scalars; a warm
    cell starts from (B_init, p_init) instead of the paper's start."""
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    one = jnp.ones((), dtype)
    g, c, D, d = (x.astype(dtype) for x in (g, c, D, d))
    Btot, N0, pmin, pmax, fmin, fmax, kappa, Rl, Rg, s_std = (
        sc[k].astype(dtype) for k in (
            "bandwidth_total", "noise_psd", "p_min", "p_max", "f_min",
            "f_max", "kappa", "local_iters", "global_rounds", "s_standard"))
    w1, w2, rho = (w[i].astype(dtype) for i in range(3))
    menu = jnp.asarray(menu, dtype)
    s_lo, s_hi = menu[0], menu[-1]
    zero = jnp.zeros_like(g)
    q = jnp.where(act, Rl * c * D / (s_std * s_std), zero)
    d = jnp.where(act, d, zero)
    n_act = jnp.sum(act.astype(dtype))

    def rate(B, p):
        Bs = jnp.maximum(B, tiny)
        return Bs * jnp.log2(one + g * p / (N0 * Bs))

    def amax(x):
        return jnp.max(jnp.where(act, x, jnp.full_like(x, -jnp.inf)))

    # ---------------------------------------------------------------- SP1
    k3 = 2.0 * w1 * Rg * kappa
    alpha = 0.5 * k3 * q
    lam_hi = 1e4 * jnp.maximum(jnp.maximum(k3 * fmax ** 3, w2 * Rg), one)

    def f_of(lam):
        return jnp.clip(_cbrt(lam / jnp.maximum(k3, tiny)), fmin, fmax)

    def s_of(lam, f):
        psi = 2.0 * alpha * f * f + 2.0 * lam * q / f
        return jnp.clip(rho * slope / jnp.maximum(psi, tiny), s_lo, s_hi)

    def makespan(lam, tt):
        f = f_of(lam)
        s = s_of(lam, f)
        return q * s * s / f + tt

    def lam_of_T(T, tt):
        fast = makespan(zero, tt) <= T
        lo, hi = _bisect(lambda m: makespan(m, tt) <= T, zero,
                         jnp.full_like(g, lam_hi), LAM_STEPS)
        return jnp.where(fast | ~act, zero, 0.5 * (lo + hi))

    def sp1(tt):
        T_lo = amax(q * s_lo * s_lo / fmax + tt)
        T_hi = 2.0 * amax(q * s_hi * s_hi / fmin + tt)
        lo, hi = _bisect(lambda T: jnp.sum(lam_of_T(T, tt)) <= w2 * Rg,
                         T_lo, T_hi, T_STEPS, geometric=True)
        T = jnp.sqrt(lo * hi)
        lam = lam_of_T(T, tt)
        f = f_of(lam)
        s_hat = s_of(lam, f)
        s = menu[jnp.argmin(jnp.abs(s_hat[:, None] - menu[None, :]), axis=1)]
        return f, s, jnp.maximum(T, amax(q * s * s / f + tt))

    # ---------------------------------------------------------------- SP2
    def sp2(f, s, T):
        slack = jnp.maximum(T - q * s * s / f, tiny)
        rmin = jnp.where(act, d / slack, zero)
        rmin = jnp.minimum(rmin, 0.95 * g * pmax / (N0 * jnp.log(2.0)))
        pmax_v = jnp.full_like(g, pmax)
        _, bmin = _bisect(lambda B: rate(B, pmax_v) >= rmin, zero,
                          jnp.full_like(g, Btot), BMIN_STEPS)
        bmin = jnp.where(act, bmin, zero)
        # floors that fill the budget leave one point: the floors, scaled
        # to the budget when they overrun it (the deadline is then missed)
        total = jnp.sum(bmin)
        tight = total >= Btot
        bmin = jnp.where(tight, bmin * (Btot / total), bmin)

        def power(B):
            p_rate = (jnp.exp2(rmin / jnp.maximum(B, tiny)) - one) \
                * N0 * B / g
            return jnp.clip(p_rate, pmin, pmax)

        def energy(B):
            p = power(B)
            return p * d / jnp.maximum(rate(B, p), tiny)

        def dE(B):
            return jax.jvp(energy, (B,), (jnp.ones_like(B),))[1]

        def B_of_mu(mu):
            lo, hi = _bisect(lambda B: dE(B) + mu >= 0.0, bmin,
                             jnp.full_like(g, Btot), B_STEPS)
            return jnp.where(act, 0.5 * (lo + hi), zero)

        mu_hi = amax(-dE(bmin)) * (1.0 + 1e-3)
        _, mu = _bisect(lambda m: jnp.sum(B_of_mu(m)) <= Btot,
                        mu_hi * 1e-12, mu_hi, MU_STEPS, geometric=True)
        B = jnp.where(tight, bmin, B_of_mu(mu))
        return B, jnp.where(act, power(B), zero)

    # ---------------------------------------------------------------- BCD
    tol = jnp.maximum(jnp.asarray(tol, dtype), 64.0 * jnp.finfo(dtype).eps)

    def step(B, p):
        tt = jnp.where(act, d / jnp.maximum(rate(B, p), tiny), zero)
        f, s, T = sp1(tt)
        B, p = sp2(f, s, T)
        return B, p, f, s

    def flat(B, p, f, s):
        v = jnp.concatenate([B, p, f, s])
        return jnp.where(jnp.concatenate([act] * 4), v, jnp.zeros_like(v))

    def cond(carry):
        k, _, _, conv = carry
        return (k < MAX_ITERS) & ~conv

    def body(carry):
        k, state, prev, _ = carry
        state = step(state[0], state[1])
        cur = flat(*state)
        rel = jnp.linalg.norm(cur - prev) / jnp.maximum(
            jnp.linalg.norm(prev), tiny)
        return k + 1, state, cur, (k >= 1) & (rel <= tol)

    B0 = jnp.where(act, jnp.where(warm, B_init.astype(dtype), Btot / n_act),
                   zero)
    p0 = jnp.where(act, jnp.where(warm, p_init.astype(dtype), pmax), zero)
    state0 = (B0, p0, jnp.where(act, fmax, zero), jnp.where(act, s_lo, zero))
    k, (B, p, f, s), _, conv = lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), state0, flat(*state0),
                     jnp.zeros((), bool)))
    return B, p, f, s, k, conv


@partial(jax.jit, static_argnames=("slope", "menu", "tol", "dtype"))
def _solve_batch(arrays, active, scalars, weights, B_init, p_init, warm,
                 slope, menu, tol, dtype):
    fn = partial(_cell, slope=slope, menu=menu, tol=tol, dtype=dtype)
    return jax.vmap(fn)(arrays["gain"], arrays["cycles"], arrays["samples"],
                        arrays["bits"], active, scalars, weights, B_init,
                        p_init, warm)


def solve(arrays: dict, active, scalars: dict, weights, accuracy: dict,
          menu, tol: float, dtype=jnp.float32, init=None,
          sharding=None) -> dict:
    """Solve a batch of cells. `arrays`: gain, cycles, samples, bits as
    (C, N); `active`: (C, N) bool, False for padding; `scalars`: (C,) per-
    cell values; `weights`: (C, 3) normalized (w1, w2, rho); `accuracy`:
    the linear A(s) through two (resolution, mAP) points; `tol`, the
    relative step at which the descent stops; `init`, for a
    re-plan that continues from an allocation the caller holds: B and p
    (C, N) and `warm` (C,) marking the cells that start from them. Returns
    numpy B, p, f, s (C, N), iterations and convergence flags (C,).
    `sharding`, if given, lays the cell axis of every input over devices."""
    (s0, s1), (a0, a1) = accuracy["resolutions"], accuracy["map"]
    slope = float((a1 - a0) / (s1 - s0))
    put = (lambda x: jax.device_put(x, sharding)) if sharding is not None \
        else jnp.asarray
    scal = {k: put(np.asarray(v)) for k, v in scalars.items()}
    shape = np.shape(active)
    if init is None:
        init = dict(B=np.zeros(shape), p=np.zeros(shape),
                    warm=np.zeros(shape[:1], bool))
    out = _solve_batch({k: put(np.asarray(v)) for k, v in arrays.items()},
                       put(np.asarray(active)), scal,
                       put(np.asarray(weights)),
                       put(np.asarray(init["B"], np.float32)),
                       put(np.asarray(init["p"], np.float32)),
                       put(np.asarray(init["warm"], bool)),
                       slope=slope, menu=tuple(float(m) for m in menu),
                       tol=float(tol), dtype=jnp.dtype(dtype))
    B, p, f, s, k, conv = jax.device_get(out)
    return dict(B=np.asarray(B), p=np.asarray(p), f=np.asarray(f),
                s=np.asarray(s), iters=np.asarray(k),
                converged=np.asarray(conv))
