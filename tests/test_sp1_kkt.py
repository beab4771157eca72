"""SP1 KKT invariants + batched-sweep parity (paper Appendix B, eqs. A.2-A.7).

Three layers:
  * deterministic KKT invariant checks (run everywhere): dual feasibility
    Sigma_n lambda_n = w2 Rg at the returned deadline, primal box
    feasibility of (f, s_hat), monotonicity of the makespan map
    T_n(lambda), and per-device makespans <= the returned T;
  * the same invariants as hypothesis property tests (degrade to skips via
    tests/_hypothesis_stub.py when hypothesis is absent);
  * parity of the batched T-grid sweep engine vs the nested-bisection
    oracle across weight regimes (energy-, latency-, accuracy-heavy), both
    LinearAccuracy and the concave log model, at f32 and f64 — the
    <=1e-5 relative-objective acceptance bound.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, st

from repro.core import Weights, make_system
from repro.core.accuracy import default_accuracy, log_fit
from repro.core.sp1 import (_coeffs, _lambda_of_T, _makespan_of_lambda,
                            _sp1_bounds, solve_sp1)
from repro.kernels import ops
from repro.kernels.ref import sp1_lambda_sum_ref
from repro.kernels.sp1_sweep import (N_CONSTS, lambda_of_T_linear,
                                     sp1_lambda_sum)


def _setup(seed=0, n=10, w=(0.5, 0.5, 1.0), **overrides):
    sysp = make_system(jax.random.PRNGKey(seed), n_devices=n, **overrides)
    weights = Weights(*w).normalized()
    B = jnp.full((n,), sysp.bandwidth_total / n)
    p = jnp.full((n,), sysp.p_max)
    return sysp, weights, B, p


def _tt(sysp, B, p):
    from repro.core.energy import rate

    return sysp.bits / jnp.maximum(rate(sysp, B, p), 1e-12)


def _sp1_objective(sysp, w, acc, f, s, T):
    alpha, _ = _coeffs(sysp, w)
    return (float(jnp.sum(alpha * s ** 2 * f ** 2))
            + float(w.w2 * sysp.global_rounds * T)
            - float(w.rho * jnp.sum(acc.value(s))))


def _continuous_objective(sysp, w, acc, B, p, method):
    """SP1 objective at the continuous KKT point: T is the s_hat makespan
    (engine differences are second-order there — the returned
    max(T, T_out_discrete) moves the w2 Rg T term first-order with the
    engine's T resolution, which is not an engine-parity signal)."""
    f, s, s_hat, _ = solve_sp1(sysp, w, acc, B, p, method=method)
    _, q = _coeffs(sysp, w)
    tt = _tt(sysp, B, p)
    T_root = float(jnp.max(q * s_hat ** 2 / jnp.maximum(f, 1e-9) + tt))
    return _sp1_objective(sysp, w, acc, f, s_hat, T_root)


def _check_kkt(sysp, w, acc, B, p, method, lam_tol=1e-3):
    """The Appendix-B KKT invariants at the solution of `solve_sp1`."""
    f, s, s_hat, T = solve_sp1(sysp, w, acc, B, p, method=method)
    f, s_hat = np.asarray(f), np.asarray(s_hat)
    tt = _tt(sysp, B, p)
    _, q = _coeffs(sysp, w)

    # primal box feasibility (A.2/A.3 clip ranges)
    assert (f >= sysp.f_min * (1 - 1e-9)).all()
    assert (f <= sysp.f_max * (1 + 1e-9)).all()
    assert (s_hat >= sysp.s_lo * (1 - 1e-9)).all()
    assert (s_hat <= sysp.s_hi * (1 + 1e-9)).all()

    # every device finishes inside the returned round deadline
    mk_hat = np.asarray(q) * s_hat ** 2 / np.maximum(f, 1e-9) + np.asarray(tt)
    assert (mk_hat <= float(T) * (1 + 1e-6)).all()
    mk_disc = np.asarray(q) * np.asarray(s) ** 2 / np.maximum(f, 1e-9) \
        + np.asarray(tt)
    assert (mk_disc <= float(T) * (1 + 1e-6)).all()

    # dual feasibility (A.7): Sigma lambda_n = w2 Rg at the continuous root
    # T_root = max_n makespan_hat (tight for every device with lambda_n > 0).
    # When T pins at its lower bound T_lo (every device at s_lo / f_max — the
    # latency-heavy regime) complementary slackness only requires
    # Sigma lambda <= w2 Rg, with the deficit absorbed by the box multipliers.
    T_root = jnp.asarray(mk_hat.max())
    lam_hi, target, T_lo, _ = _sp1_bounds(sysp, w, q, tt)
    lam = _lambda_of_T(sysp, w, acc, T_root, tt, float(lam_hi))
    total, target = float(jnp.sum(lam)), float(target)
    if float(T_root) <= float(T_lo) * (1 + 1e-9):
        assert total <= target * (1 + lam_tol)
    else:
        assert total == pytest.approx(target, rel=lam_tol)


# ---------------------------------------------------------------------------
# deterministic KKT invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sweep", "bisect"])
@pytest.mark.parametrize("wts", [(0.9, 0.1, 1.0), (0.5, 0.5, 10.0),
                                 (0.1, 0.9, 1.0)])
def test_kkt_invariants_linear(method, wts):
    sysp, w, B, p = _setup(seed=1, n=12, w=wts)
    _check_kkt(sysp, w, default_accuracy(), B, p, method)


@pytest.mark.parametrize("method", ["sweep", "bisect"])
def test_kkt_invariants_log_model(method):
    sysp, w, B, p = _setup(seed=2, n=9, w=(0.5, 0.5, 20.0))
    _check_kkt(sysp, w, log_fit(), B, p, method)


def test_makespan_monotone_decreasing_in_lambda():
    """T_n(lambda) must be nonincreasing — the premise of the inversion."""
    sysp, w, B, p = _setup(seed=3, n=8)
    tt = _tt(sysp, B, p)
    acc = default_accuracy()
    lams = jnp.logspace(-8, 8, 120)
    mk = jnp.stack([_makespan_of_lambda(sysp, w, acc,
                                        jnp.full((sysp.n,), lam), tt)
                    for lam in lams])            # (120, N)
    diffs = np.diff(np.asarray(mk), axis=0)
    assert (diffs <= 1e-9 * np.abs(np.asarray(mk[:-1]))).all()


def test_closed_form_lambda_matches_bisection():
    """lambda_of_T_linear (the sweep's exact inversion) vs `_lambda_of_T`."""
    sysp, w, B, p = _setup(seed=4, n=16)
    acc = default_accuracy()
    tt = _tt(sysp, B, p)
    _, q = _coeffs(sysp, w)
    lam_hi = float(_sp1_bounds(sysp, w, q, tt)[0])
    k3 = 2.0 * w.w1 * sysp.global_rounds * sysp.kappa
    for T in [float(jnp.max(tt)) * 1.7, 0.1, 0.5, 3.0]:
        lam_bis = _lambda_of_T(sysp, w, acc, jnp.asarray(T), tt, lam_hi)
        lam_cf = lambda_of_T_linear(jnp.asarray(T), q, tt, k3,
                                    w.rho * acc.slope, sysp.f_min, sysp.f_max,
                                    sysp.s_lo, sysp.s_hi, lam_hi)
        np.testing.assert_allclose(np.asarray(lam_cf), np.asarray(lam_bis),
                                   rtol=1e-6, atol=1e-9 * lam_hi)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
@pytest.mark.parametrize("method", ["sweep", "bisect"])
def test_pure_latency_weighting_is_finite(dtype, method):
    """w1 = 0 makes k3 = 2 w1 Rg kappa exactly 0; the division guards must
    not underflow to 0 in f32 (cbrt(0/0) = NaN used to poison the sweep's
    candidate argmin and nan the whole solve)."""
    sysp, w, B, p = _setup(seed=13, n=8, w=(0.0, 1.0, 1.0))
    sysp = jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), sysp)
    B, p = jnp.asarray(B, dtype), jnp.asarray(p, dtype)
    f, s, s_hat, T = solve_sp1(sysp, w, default_accuracy(), B, p,
                               method=method)
    assert np.isfinite(np.asarray(f)).all()
    assert np.isfinite(np.asarray(s_hat)).all()
    assert np.isfinite(float(T))


# ---------------------------------------------------------------------------
# hypothesis property tests (skip when hypothesis is absent)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(w1=st.floats(0.05, 0.95), rho=st.floats(0.0, 50.0),
       seed=st.integers(0, 31))
def test_kkt_property_sweep(w1, rho, seed):
    sysp, w, B, p = _setup(seed=seed, n=7, w=(w1, 1.0 - w1, rho))
    _check_kkt(sysp, w, default_accuracy(), B, p, "sweep")


@settings(max_examples=10, deadline=None)
@given(w1=st.floats(0.05, 0.95), rho=st.floats(0.5, 40.0),
       seed=st.integers(0, 15))
def test_kkt_property_parity(w1, rho, seed):
    """Sweep and bisection oracles agree on the objective, any weights."""
    sysp, w, B, p = _setup(seed=seed, n=6, w=(w1, 1.0 - w1, rho))
    acc = default_accuracy()
    objs = {m: _continuous_objective(sysp, w, acc, B, p, m)
            for m in ("sweep", "bisect")}
    assert objs["sweep"] == pytest.approx(objs["bisect"], rel=1e-5)


# ---------------------------------------------------------------------------
# sweep-vs-oracle parity across regimes, models, dtypes (acceptance bound)
# ---------------------------------------------------------------------------

def _cast_system(sysp, dtype):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), sysp)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
@pytest.mark.parametrize("wts", [(0.9, 0.1, 1.0),     # energy-heavy w1
                                 (0.1, 0.9, 1.0),     # latency-heavy w2
                                 (0.5, 0.5, 50.0)])   # accuracy-heavy rho
@pytest.mark.parametrize("model", ["linear", "log"])
def test_sweep_parity_regimes(dtype, wts, model):
    sysp, w, B, p = _setup(seed=7, n=24, w=wts)
    sysp = _cast_system(sysp, dtype)
    B, p = jnp.asarray(B, dtype), jnp.asarray(p, dtype)
    acc = default_accuracy() if model == "linear" else log_fit()
    out = {m: _continuous_objective(sysp, w, acc, B, p, m)
           for m in ("sweep", "bisect")}
    rel = abs(out["sweep"] - out["bisect"]) / max(abs(out["bisect"]), 1e-30)
    assert rel <= 1e-5


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_sweep_parity_large(dtype):
    """Region-scale parity: the acceptance bound at N = 8192 devices."""
    n = 8192
    sysp, w, B, p = _setup(seed=11, n=n, w=(0.5, 0.5, 1.0),
                           bandwidth_total=20e6 * n / 50)
    sysp = _cast_system(sysp, dtype)
    B, p = jnp.asarray(B, dtype), jnp.asarray(p, dtype)
    acc = default_accuracy()
    out = {m: _continuous_objective(sysp, w, acc, B, p, m)
           for m in ("sweep", "bisect")}
    rel = abs(out["sweep"] - out["bisect"]) / max(abs(out["bisect"]), 1e-30)
    assert rel <= 1e-5


# ---------------------------------------------------------------------------
# the batched op itself: Pallas kernel vs ref oracle, padded tails
# ---------------------------------------------------------------------------

def _sweep_inputs(seed=5, n=1000, w=(0.5, 0.5, 1.0)):
    sysp, wts, B, p = _setup(seed=seed, n=n, w=w,
                             bandwidth_total=20e6 * n / 50)
    tt = _tt(sysp, B, p)
    _, q = _coeffs(sysp, wts)
    lam_hi = _sp1_bounds(sysp, wts, q, tt)[0]
    consts = jnp.zeros((N_CONSTS,), tt.dtype).at[:7].set(jnp.asarray(
        [2.0 * wts.w1 * sysp.global_rounds * sysp.kappa,
         wts.rho * default_accuracy().slope, sysp.f_min, sysp.f_max,
         sysp.s_lo, sysp.s_hi, float(lam_hi)], tt.dtype))
    T_grid = jnp.geomspace(float(jnp.max(tt)) * 1.01, 1e4, 24).astype(tt.dtype)
    return T_grid, q, tt, consts


@pytest.mark.parametrize("N,block", [(1000, 256), (5, 1024), (1500, 1024)])
def test_sp1_sweep_padded_tail_matches_ref(N, block):
    """The (q=0, tt=0) tail padding must contribute exactly zero."""
    T_grid, q, tt, consts = _sweep_inputs(n=N)
    s_pal = sp1_lambda_sum(T_grid, q, tt, consts, block_n=block,
                           interpret=True, dtype=jnp.float64)
    s_ref = sp1_lambda_sum_ref(T_grid, q, tt, consts)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-12)


def test_sp1_sweep_ops_entry_matches_bisection_sum():
    """ops.sp1_lambda_sum (the production entry) vs a per-point bisection."""
    sysp, w, B, p = _setup(seed=6, n=64)
    acc = default_accuracy()
    T_grid, q, tt, consts = _sweep_inputs(seed=6, n=64)
    # _sweep_inputs used a wider-band system; rebuild tt/q for sysp instead
    tt = _tt(sysp, B, p)
    _, q = _coeffs(sysp, w)
    T_grid = jnp.geomspace(float(jnp.max(tt)) * 1.02, 1e4, 16)
    lam_hi = float(consts[6])
    s_op = ops.sp1_lambda_sum(T_grid, q, tt, consts)
    s_bis = jnp.stack([jnp.sum(_lambda_of_T(sysp, w, acc, T_grid[i], tt,
                                            lam_hi))
                       for i in range(T_grid.shape[0])])
    np.testing.assert_allclose(np.asarray(s_op), np.asarray(s_bis),
                               rtol=1e-5, atol=1e-7 * lam_hi)


@pytest.mark.parametrize("cells,N", [(1, 64), (16, 300), (3, 1500)])
def test_sp1_sweep_vmapped_cells_match_ref(cells, N):
    """Under vmap (the fleet/region/rounds solves) every cell carries its
    own T-grid, coefficients and devices; the kernel body must agree with
    the vmapped oracle cell by cell, padded tails included."""
    rows = [_sweep_inputs(seed=20 + c, n=N,
                          w=((0.2, 0.8, 1.0), (0.5, 0.5, 10.0),
                             (0.9, 0.1, 1.0))[c % 3])
            for c in range(cells)]
    T_grid, q, tt, consts = (jnp.stack(x) for x in zip(*rows))
    s_pal = jax.vmap(lambda *a: sp1_lambda_sum(
        *a, interpret=True, dtype=jnp.float64))(T_grid, q, tt, consts)
    s_ref = jax.vmap(sp1_lambda_sum_ref)(T_grid, q, tt, consts)
    assert s_pal.shape == (cells, T_grid.shape[1])
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-12)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_cbrt_nonneg_matches_cbrt(dtype):
    """The exp/log cube root the kernel uses (Mosaic has no cbrt) is within
    2 ulps of the true cube root (numpy's, in f64), exactly 0 at 0 and inf
    at inf."""
    from repro.kernels.sp1_sweep import _cbrt_nonneg

    fi = jnp.finfo(dtype)
    x = jnp.concatenate([
        jnp.geomspace(float(fi.tiny), float(fi.max) / 2, 4001, dtype=dtype),
        jnp.asarray([0.0, 1.0, 8.0, 27.0, jnp.inf], dtype)])
    got = np.asarray(_cbrt_nonneg(x), np.float64)
    want = np.cbrt(np.asarray(x, np.float64))
    assert got[-5] == 0.0 and got[-1] == np.inf
    assert list(got[-4:-1]) == [1.0, 2.0, 3.0]
    finite = np.isfinite(want) & (want > 0)
    np.testing.assert_allclose(got[finite], want[finite],
                               rtol=2 * float(fi.eps))


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_sweep_edge_lanes_exact(dtype):
    """Pad lanes (q = 0, tt = 0) add exactly 0 to every candidate deadline,
    and the pure-latency weighting (k3 = 0, so lam = 0 puts f at its clip)
    stays finite — through the kernel body and the oracle alike."""
    T_grid, q, tt, consts = _sweep_inputs(seed=7, n=40)
    T_grid, q, tt = (x.astype(dtype) for x in (T_grid, q, tt))
    zeros = jnp.zeros((88,), dtype)
    for k3 in (float(consts[0]), 0.0):
        c = consts.at[0].set(k3).astype(dtype)
        lam = lambda_of_T_linear(T_grid[:, None], q[None, :], tt[None, :],
                                 *c[:7])
        lam_pad = lambda_of_T_linear(
            T_grid[:, None], jnp.concatenate([q, zeros])[None, :],
            jnp.concatenate([tt, zeros])[None, :], *c[:7])
        assert np.isfinite(np.asarray(lam)).all()
        np.testing.assert_array_equal(np.asarray(lam_pad[:, :40]),
                                      np.asarray(lam))
        assert np.all(np.asarray(lam_pad[:, 40:]) == 0.0)
        s_ref = sp1_lambda_sum_ref(T_grid, q, tt, c)
        s_pal = sp1_lambda_sum(T_grid, q, tt, c, interpret=True, dtype=dtype)
        assert np.isfinite(np.asarray(s_pal)).all()
        np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                                   rtol=1e-12 if dtype == jnp.float64
                                   else 1e-5)
