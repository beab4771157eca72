"""Batched SP1 dual sweep kernel: Sigma_n lambda_n(T) over a whole T-grid.

SP1's KKT system (paper eqs. A.2-A.7) is solved by inverting the per-device
makespan map lambda -> T_n(lambda) and then finding the T at which
Sigma_n lambda_n(T) = w2 Rg. The seed solved this with a nested 56x56 scalar
bisection; this kernel evaluates the inner inversion for M candidate
deadlines over N devices in ONE pass — the SP1 analogue of the SP2
`waterfill` dual sweep, and the op `core.sp1`'s T-sweep drives.

For the paper's LinearAccuracy model the inner inversion is EXACT: with
k3 = 2 w1 Rg kappa and alpha = w1 Rg kappa q, the KKT stationarity gives
f(lam) = clip((lam/k3)^(1/3), fmin, fmax) and
s(lam) = clip(rho k / psi, s_lo, s_hi), psi = 2 alpha f^2 + 2 lam q / f, so
the compute time q s^2/f is piecewise closed-form in lam. Each clipping
regime inverts in closed form; we evaluate every regime's candidate, push it
through the exact forward map, and keep the smallest lambda among the
candidates with minimal makespan error (the bisection's left-edge convention
on flat segments, and exactly 0 for devices already meeting the deadline).

Layout: grid (N/bn,) over device blocks; every operand is 2-D so that each
block's last two dims are either full or (8, 128)-tiled — the T-grid (M, 1)
whole, the scalar coefficients (1, N_CONSTS) whole in SMEM, q/tt (1, bn)
lane blocks, and the (M, 1) partial sums accumulated across the sequential
grid steps. `vmap`
(the fleet, region and rounds solves) prepends a squeezed cell dimension to
every block and a cell axis to the grid, which keeps that rule intact at any
number of cells. The math uses only ops Mosaic lowers: the cube root and
the fractional powers go through exp/log (see `_cbrt_nonneg`).

Oracle: kernels.ref.sp1_lambda_sum_ref (same closed form at full input
precision); parity vs the nested bisection is tested in tests/test_sp1_kkt.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# consts vector layout fed to the kernel: index -> meaning
N_CONSTS = 8   # [k3, rho_slope, f_min, f_max, s_lo, s_hi, lam_hi, unused]

_LANES = 128


def _cbrt_nonneg(x):
    """cbrt for x >= 0 from exp/log (Mosaic has no cbrt lowering), with one
    Newton step restoring ~1 ulp. x = 0 gives exactly 0 (log 0 = -inf) and
    x = inf gives inf; both skip the Newton step, which would be 0/0 there."""
    y = jnp.exp(jnp.log(x) * (1.0 / 3.0))
    ok = (y > 0.0) & (y < jnp.inf)
    ys = jnp.where(ok, y, 1.0)
    return jnp.where(ok, ys - (ys - x / (ys * ys)) * (1.0 / 3.0), y)


def lambda_of_T_linear(T, q, tt, k3, rhok, f_min, f_max, s_lo, s_hi, lam_hi):
    """Exact lambda_n(T) for LinearAccuracy; pure jnp, broadcasts over any
    shared shape of (T, q, tt). Scalars may be traced (per-cell leaves).

    Enumerates the clipping regimes of (f, s):
      f = F in {fmin, fmax}, s interior:  s = sqrt(t_c F / q),
          lam = (rhok/s - 2 alpha F^2) F / (2 q)
      s = S in {s_lo, s_hi}, f interior:  f = q S^2 / t_c, lam = k3 f^3
      both interior:  psi = 6 alpha f^2  =>  f^5 = q rhok^2 / (36 alpha^2 t_c)
    plus lam = 0 (device already meets the deadline). Candidates are clipped
    to [0, lam_hi] (nan -> lam_hi: unreachable t_c saturates the bracket like
    the bisection does), validated through the exact forward makespan, and
    the smallest lambda among the error-minimizing candidates is returned.
    """
    dt = jnp.result_type(T, q, tt)
    # division guards must be dtype-aware: a literal 1e-300 underflows to 0
    # in f32 and w1 == 0 (k3 == 0, a valid pure-latency weighting) would
    # turn the lam=0 candidate into cbrt(0/0) = NaN, poisoning the argmin
    tiny = jnp.asarray(jnp.finfo(dt).tiny, dt)
    t_c = jnp.maximum(T - tt, tiny)           # target compute time
    q_safe = jnp.maximum(q, tiny)
    alpha = 0.5 * k3 * q

    def makespan_err(lam):                    # exact forward map, vs target
        f = jnp.clip(_cbrt_nonneg(lam / jnp.maximum(k3, tiny)), f_min, f_max)
        psi = 2.0 * alpha * f ** 2 + 2.0 * lam * q / jnp.maximum(f, 1e-9)
        s = jnp.clip(rhok / jnp.maximum(psi, tiny), s_lo, s_hi)
        return jnp.abs(q * s ** 2 / jnp.maximum(f, 1e-9) - t_c)

    def cand_f_clipped(F):                    # f pinned at a box edge
        s = jnp.sqrt(t_c * F / q_safe)
        return (rhok / jnp.maximum(s, tiny) - 2.0 * alpha * F ** 2) \
            * F / (2.0 * q_safe)

    def cand_s_clipped(S):                    # s pinned at a box edge
        f = q * S ** 2 / t_c
        return k3 * f ** 3

    # both interior: f^5 = q rhok^2 / (36 alpha^2 t_c) with alpha = k3 q / 2,
    # i.e. f = (rhok / (3 k3))^(2/5) * (q t_c)^(-1/5), taken in log space.
    # Factored this way so kappa-scale coefficients never square: alpha^2
    # ~ 1e-45 underflows f32 (the chip dtype) even though f is representable.
    f6 = jnp.exp(0.4 * jnp.log(rhok / jnp.maximum(3.0 * k3, tiny))
                 - 0.2 * jnp.log(jnp.maximum(q * t_c, tiny)))
    cands = [jnp.zeros_like(t_c),
             cand_f_clipped(f_min), cand_f_clipped(f_max),
             cand_s_clipped(s_lo), cand_s_clipped(s_hi),
             k3 * f6 ** 3]
    cands = [jnp.where(jnp.isnan(c), lam_hi, jnp.clip(c, 0.0, lam_hi))
             for c in cands]
    # candidates stay separate arrays (no stacked axis, which Mosaic would
    # have to relayout); min/select over them is exact either way
    errs = [makespan_err(c) for c in cands]
    best = functools.reduce(jnp.minimum, errs)
    thresh = best * (1.0 + 1e-6) + tiny
    lam = functools.reduce(jnp.minimum, [jnp.where(e <= thresh, c, jnp.inf)
                                         for c, e in zip(cands, errs)])
    # Strictly unattainable deadline (t_c below the q s_lo^2/f_max makespan
    # floor): every candidate ties at the floor, and the min-lambda rule
    # would pick the left edge of the clipped-flat region; the bisection
    # saturates its bracket instead. Match it so the closed form is a
    # drop-in for `_lambda_of_T` over the whole T axis, not just the
    # attainable range the sweep queries. (f and s agree either way — both
    # lambdas sit in the f=f_max, s=s_lo clip regime.)
    return jnp.where(q * s_lo ** 2 / jnp.maximum(f_max, 1e-9) > t_c,
                     lam_hi, lam)


def _sp1_kernel(T_ref, c_ref, q_ref, tt_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = [c_ref[0, j] for j in range(7)]       # SMEM scalars
    lam = lambda_of_T_linear(T_ref[...], q_ref[...], tt_ref[...], *k)
    out_ref[...] += jnp.sum(lam, axis=1, keepdims=True)   # (M, bn) -> (M, 1)


def sp1_lambda_sum(T_grid: jax.Array, q: jax.Array, tt: jax.Array,
                   consts: jax.Array, *, block_n: int = 1024,
                   interpret: bool = False,
                   dtype=jnp.float32) -> jax.Array:
    """Sigma_n lambda_n(T) per candidate: T_grid (M,), q/tt (N,),
    consts (N_CONSTS,) -> (M,). Any N: devices are split into lane blocks of
    min(block_n, N rounded up to 128), and the tail is padded with
    (q=0, tt=0) lanes, for which every candidate ties at makespan 0 and the
    min-lambda rule returns exactly 0 — an implicit mask of the partial sum.

    dtype: in-kernel compute/output dtype, as for `waterfill.waterfill_gprime`.
    """
    if block_n % _LANES:
        raise ValueError(f"block_n must be a multiple of {_LANES}, "
                         f"got {block_n}")
    N = q.shape[0]
    bn = min(block_n, -(-N // _LANES) * _LANES)
    rem = (-N) % bn
    if rem:
        q = jnp.concatenate([q, jnp.zeros((rem,), q.dtype)])
        tt = jnp.concatenate([tt, jnp.zeros((rem,), tt.dtype)])
        N += rem
    M = T_grid.shape[0]
    out = pl.pallas_call(
        _sp1_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((M, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, N_CONSTS), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((M, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), dtype),
        interpret=interpret,
        name="sp1_lambda_sum",
    )(T_grid.astype(dtype).reshape(M, 1),
      consts.astype(dtype).reshape(1, N_CONSTS),
      q.astype(dtype).reshape(1, N), tt.astype(dtype).reshape(1, N))
    return out[:, 0]
