"""Regression + parity tests for the jit-resident BCD stack:
  * allocate_fixed_deadline(max_iters=0) returns nan instead of IndexError
  * waterfill_gprime accepts N not divisible by block_n (padded tail block)
  * the kernelized thm2 dual search matches the old scalar float() bisection
  * allocate_fleet (vmap'd BCD) is consistent with per-cell allocate
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Weights, allocate, allocate_fixed_deadline,
                        allocate_fleet, feasible, make_fleet, make_system,
                        stack_systems)
from repro.core.lambertw import lambertw0
from repro.core.sp2 import G, _clamp_rmin, solve_sp2_v2_thm2
from repro.kernels import ops, ref
from repro.kernels.waterfill import waterfill_gprime as waterfill_raw


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_fixed_deadline_zero_iters_returns_nan():
    """max_iters=0 used to raise IndexError on history[-1]."""
    sysp = make_system(jax.random.PRNGKey(0), n_devices=4)
    res = allocate_fixed_deadline(sysp, Weights(0.99, 0.01, 1.0), 100.0,
                                  max_iters=0)
    assert res.iters == 0
    assert res.history == []
    assert np.isnan(res.objective)
    # the initial allocation is handed back untouched
    assert res.allocation.bandwidth.shape == (4,)


def test_allocate_zero_iters_returns_nan():
    sysp = make_system(jax.random.PRNGKey(0), n_devices=4)
    res = allocate(sysp, Weights(0.5, 0.5, 1.0), max_iters=0)
    assert res.iters == 0 and res.history == [] and np.isnan(res.objective)


def test_warm_start_converges_in_fewer_iterations():
    """allocate(init=...) on a slightly perturbed system must beat the cold
    start: the warm-started BCD re-uses the previous solution (the round-
    dynamics engine's per-round re-allocation path)."""
    w = Weights(0.5, 0.5, 1.0)
    sysp = make_system(jax.random.PRNGKey(40), n_devices=12)
    # tol=1e-8: at the default 1e-6 the cold BCD already converges in ~2
    # iterations and there is no headroom to demonstrate the warm start
    base = allocate(sysp, w, max_iters=40, tol=1e-8)
    assert base.converged
    # ~2% channel perturbation, as between consecutive correlated rounds
    bump = 1.0 + 0.02 * jnp.sin(jnp.arange(12.0))
    sys2 = sysp.replace(gain=sysp.gain * bump)
    cold = allocate(sys2, w, max_iters=40, tol=1e-8)
    warm = allocate(sys2, w, max_iters=40, tol=1e-8, init=base.allocation)
    assert warm.converged and cold.converged
    assert warm.iters < cold.iters, (warm.iters, cold.iters)
    # and lands at the same objective
    assert warm.objective == pytest.approx(cold.objective, rel=1e-4)


def test_allocate_fleet_warm_start_init():
    """allocate_fleet(init=...) warm-starts every cell; a perturbed fleet
    re-solve from the previous FleetResult takes fewer iterations."""
    w = Weights(0.5, 0.5, 1.0)
    fleet = make_fleet(jax.random.PRNGKey(41), n_cells=4, n_devices=16)
    base = allocate_fleet(fleet, w, max_iters=40, tol=1e-8)
    fleet2 = fleet.replace(gain=fleet.gain * 1.02)
    cold = allocate_fleet(fleet2, w, max_iters=40, tol=1e-8)
    warm = allocate_fleet(fleet2, w, max_iters=40, tol=1e-8,
                          init=base.allocation)
    # the warm start converges everywhere; cold may still be grinding at the
    # iteration cap — that asymmetry is the point
    assert bool(jnp.all(warm.converged))
    assert int(jnp.sum(warm.iters)) < int(jnp.sum(cold.iters))
    np.testing.assert_allclose(np.asarray(warm.objective),
                               np.asarray(cold.objective), rtol=1e-4)


@pytest.mark.parametrize("N,block", [(1000, 256), (7, 1024), (1500, 1024)])
def test_waterfill_padded_tail_matches_ref(N, block):
    """N % block_n != 0 used to hard-assert; the padded tail must be a no-op."""
    key = jax.random.PRNGKey(5)
    j = jnp.abs(jax.random.normal(key, (N,))) * 1e-3 + 1e-5
    rmin = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (N,))) * 1e5
    mu = jnp.logspace(-8, 0, 16)
    g_pal = ops.waterfill_gprime(mu, j, rmin, 20e6, block_n=block,
                                 impl="interpret")
    g_ref = ref.waterfill_gprime_ref(mu, j, rmin, 20e6)
    err = np.abs(np.asarray(g_pal - g_ref)) / np.maximum(np.abs(np.asarray(g_ref)), 1.0)
    # f32 kernel vs f64 oracle; the <=1e-5 acceptance bound is checked at
    # matched precision in test_waterfill_f64_interpret_parity
    assert err.max() <= 2e-5


def test_waterfill_f64_interpret_parity():
    """Acceptance bound: kernel vs oracle to <= 1e-5 relative error."""
    key = jax.random.PRNGKey(9)
    N = 768
    j = jnp.abs(jax.random.normal(key, (N,))) * 1e-3 + 1e-5
    rmin = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (N,))) * 1e5
    mu = jnp.logspace(-8, 0, 32)
    g = waterfill_raw(mu, j, rmin, 20e6, block_n=256, interpret=True,
                      dtype=jnp.float64)
    g_ref = ref.waterfill_gprime_ref(mu, j, rmin, 20e6)
    err = np.abs(np.asarray(g - g_ref)) / np.maximum(np.abs(np.asarray(g_ref)), 1.0)
    assert err.max() <= 1e-5


# ---------------------------------------------------------------------------
# thm2 kernelized dual search vs the old scalar bisection
# ---------------------------------------------------------------------------

def _scalar_bisection_thm2(sysp, nu, beta, rmin):
    """The pre-refactor host-side path: 200-step bracket expansion + 96
    float() bisections on g'(mu), then the Theorem-2 closed forms."""
    g_lin, d, N0 = np.asarray(sysp.gain), np.asarray(sysp.bits), sysp.noise_psd
    nu_np, beta_np = np.asarray(nu), np.asarray(beta)
    rm = np.asarray(rmin)
    j = nu_np * d * N0 / g_lin

    def gprime(mu):
        wv = np.asarray(lambertw0(jnp.asarray((mu - j) / (np.e * j))))
        return float(np.sum(rm * np.log(2.0) / np.maximum(wv + 1.0, 1e-12))
                     - sysp.bandwidth_total)

    lo, hi = 1e-30, float(j.max()) * 2.0 + 1.0
    for _ in range(200):
        if gprime(hi) < 0.0:
            break
        hi *= 4.0
    for _ in range(96):
        mid = 0.5 * (lo + hi)
        if gprime(mid) > 0.0:
            lo = mid
        else:
            hi = mid
    mu = 0.5 * (lo + hi)

    W = np.asarray(lambertw0(jnp.asarray((mu - j) / (np.e * j))))
    a_val = np.where(np.abs(W) > 1e-12,
                     (mu - j) * np.log(2.0) / np.where(np.abs(W) < 1e-12, 1.0, W),
                     np.e * j * np.log(2.0))
    tau = np.maximum(a_val - nu_np * beta_np, 0.0)
    a = nu_np * beta_np + tau
    Lam = np.maximum(a * g_lin / (N0 * d * nu_np * np.log(2.0)), 1.0 + 1e-12)
    B_opt = rm / np.log2(Lam)
    total = float(B_opt.sum())
    if total > sysp.bandwidth_total:
        B_opt = B_opt * sysp.bandwidth_total / total
    p_opt = np.clip((Lam - 1.0) * N0 * B_opt / g_lin, sysp.p_min, sysp.p_max)
    return p_opt, B_opt


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_thm2_kernelized_matches_scalar_bisection(seed):
    n = 8
    sysp = make_system(jax.random.PRNGKey(seed), n_devices=n)
    B0 = jnp.full((n,), sysp.bandwidth_total / n)
    p0 = jnp.full((n,), sysp.p_max)
    rmin = _clamp_rmin(sysp, 0.9 * G(sysp, p0, B0))
    w = Weights(0.5, 0.5, 1.0).normalized()
    rate0 = G(sysp, p0, B0)
    nu = w.w1 * sysp.global_rounds / rate0
    beta = sysp.p_max * sysp.bits / rate0

    p_k, B_k = solve_sp2_v2_thm2(sysp, w, nu, beta, rmin)
    p_s, B_s = _scalar_bisection_thm2(sysp, nu, beta, rmin)
    np.testing.assert_allclose(np.asarray(B_k), B_s, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(p_k), p_s, rtol=1e-4)


def test_thm2_dual_bracket_covers_tight_deadlines():
    """Tight deadlines push the dual root to mu ~ j * exp(sum(rmin) ln2 / B);
    the sweep's bracket is sized from that estimate, so regimes far above any
    fixed cap (here ~100 nats, root ~ 1e33) must still match the scalar
    oracle."""
    from repro.core.sp2 import _thm2_dual_mu

    n = 50
    sysp = make_system(jax.random.PRNGKey(0), n_devices=n)
    rmin = jnp.full((n,), 100.0 * sysp.bandwidth_total / (n * np.log(2.0)))
    w = Weights(0.5, 0.5, 1.0).normalized()
    rate0 = G(sysp, jnp.full((n,), sysp.p_max),
              jnp.full((n,), sysp.bandwidth_total / n))
    nu = w.w1 * sysp.global_rounds / rate0
    j = nu * sysp.bits * sysp.noise_psd / sysp.gain
    mu = float(_thm2_dual_mu(sysp, j, rmin))

    def gprime(m):
        wv = np.asarray(lambertw0(jnp.asarray((m - np.asarray(j)) / (np.e * np.asarray(j)))))
        return float(np.sum(np.asarray(rmin) * np.log(2.0)
                            / np.maximum(wv + 1.0, 1e-12)) - sysp.bandwidth_total)

    assert mu > 1e30                       # far above any fixed 4**40 cap
    assert gprime(mu * 0.999) > 0 > gprime(mu * 1.001)   # brackets the root


def test_thm2_is_jittable():
    """The dual search must be device-resident: tracing it must not leak a
    concretization error (the old float() path could not be jitted)."""
    n = 6
    sysp = make_system(jax.random.PRNGKey(3), n_devices=n)
    B0 = jnp.full((n,), sysp.bandwidth_total / n)
    p0 = jnp.full((n,), sysp.p_max)
    rmin = _clamp_rmin(sysp, 0.9 * G(sysp, p0, B0))
    w = Weights(0.5, 0.5, 1.0).normalized()
    rate0 = G(sysp, p0, B0)
    nu = w.w1 * sysp.global_rounds / rate0
    beta = sysp.p_max * sysp.bits / rate0
    f = jax.jit(lambda nu_, beta_, rm_: solve_sp2_v2_thm2(sysp, w, nu_, beta_, rm_))
    p_j, B_j = f(nu, beta, rmin)
    p_e, B_e = solve_sp2_v2_thm2(sysp, w, nu, beta, rmin)
    np.testing.assert_allclose(np.asarray(B_j), np.asarray(B_e), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(p_j), np.asarray(p_e), rtol=1e-12)


# ---------------------------------------------------------------------------
# fleet API
# ---------------------------------------------------------------------------

def test_fleet_matches_per_cell_allocate():
    """vmap'd BCD must agree with the scalar path cell by cell."""
    fleet = make_fleet(jax.random.PRNGKey(0), n_cells=3, n_devices=5)
    w = Weights(0.5, 0.5, 10.0)
    fr = allocate_fleet(fleet, w, max_iters=4)
    assert fr.objective.shape == (3,)
    for c in range(3):
        cell = jax.tree_util.tree_map(lambda x: x[c], fleet)
        single = allocate(cell, w, max_iters=4)
        assert single.iters == int(fr.iters[c])
        assert single.converged == bool(fr.converged[c])
        np.testing.assert_allclose(np.asarray(fr.allocation.bandwidth[c]),
                                   np.asarray(single.allocation.bandwidth),
                                   rtol=1e-10)
        np.testing.assert_allclose(np.asarray(fr.allocation.power[c]),
                                   np.asarray(single.allocation.power),
                                   rtol=1e-10)
        assert float(fr.objective[c]) == pytest.approx(single.objective,
                                                       rel=1e-10)
        assert feasible(cell, jax.tree_util.tree_map(lambda x: x[c],
                                                     fr.allocation))


def test_fleet_ledger_shape_and_nan_tail():
    fleet = make_fleet(jax.random.PRNGKey(1), n_cells=2, n_devices=4)
    fr = allocate_fleet(fleet, Weights(0.5, 0.5, 1.0), max_iters=6)
    assert fr.history.shape == (2, 6, len(fr.columns))
    for c in range(2):
        it = int(fr.iters[c])
        led = np.asarray(fr.history[c])
        assert np.isfinite(led[:it]).all()
        assert np.isnan(led[it:]).all()


def test_stack_systems_accepts_heterogeneous_scalars():
    """bandwidth_total/p_max & co are traced leaves now: mixed cell classes
    stack into (C,) scalar leaves instead of raising."""
    s1 = make_system(jax.random.PRNGKey(0), n_devices=4)
    s2 = make_system(jax.random.PRNGKey(1), n_devices=4, bandwidth_total=10e6,
                     p_max=0.01)
    fleet = stack_systems([s1, s2])
    np.testing.assert_allclose(np.asarray(fleet.bandwidth_total),
                               [s1.bandwidth_total, 10e6])
    np.testing.assert_allclose(np.asarray(fleet.p_max), [s1.p_max, 0.01])
    assert fleet.gain.shape == (2, 4)


def test_stack_systems_rejects_mismatched_resolutions():
    """The discrete s-menu is the remaining static aux datum: it fixes the
    rounding table shape, so cells must agree on it."""
    s1 = make_system(jax.random.PRNGKey(0), n_devices=4)
    s2 = make_system(jax.random.PRNGKey(1), n_devices=4,
                     resolutions=(160.0, 320.0))
    with pytest.raises(ValueError):
        stack_systems([s1, s2])


def test_heterogeneous_fleet_matches_per_cell_allocate():
    """A stacked fleet of cells with differing bandwidth/power budgets must
    agree with per-cell `allocate` element-wise (the vmap'd solve reads the
    per-cell scalar leaves, not a shared static config)."""
    fleet = make_fleet(jax.random.PRNGKey(3), n_cells=3, n_devices=5,
                       bandwidth_total=[8e6, 20e6, 45e6],
                       p_max=[0.01, 0.0158, 0.025])
    np.testing.assert_allclose(np.asarray(fleet.bandwidth_total),
                               [8e6, 20e6, 45e6])
    w = Weights(0.5, 0.5, 5.0)
    fr = allocate_fleet(fleet, w, max_iters=4)
    for c in range(3):
        cell = jax.tree_util.tree_map(lambda x: x[c], fleet)
        single = allocate(cell, w, max_iters=4)
        assert single.iters == int(fr.iters[c])
        np.testing.assert_allclose(np.asarray(fr.allocation.bandwidth[c]),
                                   np.asarray(single.allocation.bandwidth),
                                   rtol=1e-10)
        np.testing.assert_allclose(np.asarray(fr.allocation.power[c]),
                                   np.asarray(single.allocation.power),
                                   rtol=1e-10)
        np.testing.assert_allclose(np.asarray(fr.allocation.freq[c]),
                                   np.asarray(single.allocation.freq),
                                   rtol=1e-10)
        assert float(fr.objective[c]) == pytest.approx(single.objective,
                                                       rel=1e-10)
        assert feasible(cell, jax.tree_util.tree_map(lambda x: x[c],
                                                     fr.allocation))
    # the bandwidth budgets actually differ cell to cell in the solution
    sums = np.asarray(jnp.sum(fr.allocation.bandwidth, axis=1))
    np.testing.assert_allclose(sums, [8e6, 20e6, 45e6], rtol=1e-3)


def test_make_fleet_rejects_wrong_length_per_cell_override():
    with pytest.raises(ValueError):
        make_fleet(jax.random.PRNGKey(0), n_cells=3, n_devices=4,
                   bandwidth_total=[10e6, 20e6])


@pytest.mark.parametrize("sp1_method,sp2_method",
                         [("sweep", "direct"), ("bisect", "direct"),
                          ("sweep", "jong")])
def test_allocate_f32_system_under_x64(sp1_method, sp2_method):
    """An f32-leaf system must solve in f32 even with x64 enabled: the static
    resolutions menu and the mu-search literals are pinned to the system
    dtype, else the BCD while_loop carry silently promotes and trips the
    equal-carry-types check."""
    sysp = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                  make_system(jax.random.PRNGKey(0),
                                              n_devices=6))
    res = allocate(sysp, Weights(0.5, 0.5, 1.0), max_iters=4,
                   sp1_method=sp1_method, sp2_method=sp2_method)
    assert res.allocation.bandwidth.dtype == jnp.float32
    assert np.isfinite(res.objective)


def test_fleet_convergence_rate_default_config():
    """Regression for the 12/64 fleet convergence bug: with the dtype-aware
    rel-step floor (the raw 1e-6 tol sat below the f32 iterate noise floor)
    at least 90% of cells must report convergence on the default 8x256
    fleet config."""
    C, N = 8, 256
    fleet = make_fleet(jax.random.PRNGKey(31), n_cells=C, n_devices=N,
                       bandwidth_total=20e6 * N / 50)
    res = allocate_fleet(fleet, Weights(0.5, 0.5, 1.0), max_iters=12)
    conv = int(jnp.sum(res.converged))
    assert conv >= int(0.9 * C), f"only {conv}/{C} cells converged"
    # converged cells actually stopped early (the cap did not bind)
    assert int(jnp.max(res.iters)) < 12


def test_allocate_history_is_device_resident_ledger():
    """History rows materialize once, after the loop: iter indices contiguous,
    objective monotone nonincreasing, rel_step recorded."""
    sysp = make_system(jax.random.PRNGKey(2), n_devices=6)
    res = allocate(sysp, Weights(0.5, 0.5, 1.0), max_iters=6)
    assert [h["iter"] for h in res.history] == list(range(1, res.iters + 1))
    objs = [h["objective"] for h in res.history]
    assert all(objs[i + 1] <= objs[i] + 1e-6 for i in range(len(objs) - 1))
    assert all("rel_step" in h for h in res.history)
