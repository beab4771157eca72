"""Jit-resident round-dynamics engine.

The paper's system model (Fig. 1) is a *repeated* FL loop; the static
allocator optimizes one round against expected channel gains and multiplies
the ledger by R_g. This engine runs the R rounds explicitly as **one jitted
`lax.scan`** — per round it

  1. samples per-device channel gains (`core.channel.sample_gain`, or the
     AR(1) Gauss-Markov drift `core.channel.drift_shadowing`),
  2. re-solves the allocation with a **warm-started BCD** (the previous
     round's allocation is the init, so re-allocation costs a couple of
     iterations instead of a cold solve),
  3. applies a participation model (straggler deadline misses, random
     dropouts, async staleness — see `dynamics.participation`), and
  4. accumulates the realized energy/time/accuracy-proxy ledger into a
     fixed-size (R, cols) array on device — no host syncs inside the scan.

`run_rounds_fleet` vmaps the engine across stacked cells (see
`core.bcd.stack_systems`): R rounds x C cells x N devices is a single XLA
program. With static channels, full participation and no staleness the
per-round ledger reproduces the allocate-once ledger of `fl/simulator.py`
(parity-tested to <=1e-5). ROADMAP: "Channel dynamics" + "Async FL rounds".
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import energy as en
from repro.core.accuracy import AccuracyModel
from repro.core.bcd import (_COUNTER_COLS, _allocate_impl, _init_carry_state,
                            initial_allocation)
from repro.core.channel import drift_shadowing, sample_gain, shadowing_to_gain
from repro.core.types import Allocation, SystemParams, Weights

from .config import ROUND_COLS, RoundsConfig, RoundsResult
from .participation import queue_step, staleness_of

Array = jnp.ndarray


def _masked_max(x: Array, mask: Array) -> Array:
    return jnp.max(jnp.where(mask, x, jnp.zeros((), x.dtype)))


def _cell_engine(sys: SystemParams, warr: Array, acc: AccuracyModel,
                 key: jax.Array, state0, cfg: RoundsConfig, kernel: str):
    """One cell's R-round scan. Returns (final BCD state, ledger (R, cols),
    staleness codes (R, N) int32, realized gains (R, N), allocated
    resolutions (R, N)). `kernel` is the SP1 sweep kernel's mode, resolved
    outside jit (`kernels.ops.kernel_mode`)."""
    dtype = state0[0].dtype
    n = sys.gain.shape[0]
    K = cfg.max_staleness
    Dw = jnp.asarray(sys.samples, dtype)
    w_total = jnp.maximum(jnp.sum(Dw), jnp.finfo(dtype).tiny)
    wobj = Weights(warr[0], warr[1], warr[2])
    decay = jnp.asarray(cfg.staleness_decay, dtype)

    k_shadow, k_rounds = jax.random.split(key)
    shadow0 = (jax.random.normal(k_shadow, (n,), dtype)
               if cfg.channel_mode == "markov" else jnp.zeros((n,), dtype))
    keys = jax.random.split(k_rounds, cfg.rounds)

    def step(carry, kr):
        state, shadow, qw, qu = carry
        k_gain, k_drop = jax.random.split(kr)

        # (1) channel realization for this round
        if cfg.channel_mode == "static":
            g = sys.gain
        elif cfg.channel_mode == "iid":
            g = sample_gain(k_gain, sys.gain, cfg.shadowing_db)
        else:  # markov
            shadow = drift_shadowing(k_gain, shadow, cfg.drift_rho)
            g = shadowing_to_gain(sys.gain, shadow, cfg.shadowing_db)
        sys_r = sys.replace(gain=g)

        # (2) warm-started re-allocation (bcd_iters=0 keeps the carried init)
        state_in = state if cfg.warm_start else _init_carry_state(
            sys_r, initial_allocation(sys_r))
        B, p, f, s, s_hat, T, iters, conv, _, counters = _allocate_impl(
            sys_r, warr, acc, state_in, cfg.bcd_iters, cfg.bcd_tol,
            cfg.sp1_method, cfg.sp2_method, cfg.sp2_iters, kernel)
        state = (B, p, f, s, s_hat, T)
        alloc = Allocation(bandwidth=B, power=p, freq=f, resolution=s,
                           s_relaxed=s_hat, T=T)

        # realized per-device round time / energy under this round's gains
        t_dev = (en.t_cmp(sys_r, f, s) + en.t_trans(sys_r, B, p)).astype(dtype)
        e_dev = (en.e_cmp(sys_r, f, s) + en.e_trans(sys_r, B, p)).astype(dtype)
        util_dev = jnp.asarray(acc.value(s), dtype)

        # (3) participation
        if cfg.dropout_prob > 0.0:
            active = ~jax.random.bernoulli(k_drop, cfg.dropout_prob, (n,))
        else:
            active = jnp.ones((n,), bool)
        if sys.active is not None:   # padded-out lanes never participate
            active &= sys.active
        deadline = jnp.asarray(cfg.deadline_slack, dtype) * T

        if cfg.participation == "full":
            late = jnp.zeros((n,), bool)
            arrived_u = jnp.sum(jnp.where(active, util_dev, 0.0))
            arrived_w = jnp.sum(jnp.where(active, Dw, 0.0))
            time_r = _masked_max(t_dev, active)
            code = jnp.where(active, 0, -1).astype(jnp.int32)
        else:
            # lateness and the queued staleness must agree, so both derive
            # from the same bucketing (a one-ulp-late device would otherwise
            # get late=True with kst=0 and desync the ledger from the queue)
            kst = staleness_of(t_dev, deadline, K)
            late = active & (kst > 0)
            ontime = active & ~late
            closes_at = jnp.where(jnp.any(late), deadline,
                                  _masked_max(t_dev, ontime))
            if cfg.participation == "drop":
                arrived_u = jnp.sum(jnp.where(ontime, util_dev, 0.0))
                arrived_w = jnp.sum(jnp.where(ontime, Dw, 0.0))
                time_r = closes_at
                code = jnp.where(ontime, 0, -1).astype(jnp.int32)
            else:  # stale: late mass arrives k rounds later, decay^k weighted
                disc = decay ** kst.astype(dtype)
                qw, qu, pop_w, pop_u = queue_step(
                    qw, qu, jnp.maximum(kst - 1, 0),
                    jnp.where(late, Dw * disc, 0.0),
                    jnp.where(late, util_dev * disc, 0.0))
                arrived_u = jnp.sum(jnp.where(ontime, util_dev, 0.0)) + pop_u
                arrived_w = jnp.sum(jnp.where(ontime, Dw, 0.0)) + pop_w
                time_r = closes_at
                code = jnp.where(active, jnp.where(late, kst, 0), -1)
                code = code.astype(jnp.int32)

        # (4) realized ledger row
        row = jnp.stack([
            en.objective(sys_r, wobj, acc, alloc).astype(dtype),
            jnp.sum(jnp.where(active, e_dev, 0.0)),
            time_r,
            arrived_u,
            arrived_w / w_total,
            jnp.sum(late).astype(dtype),
            jnp.sum(~active).astype(dtype),
            iters.astype(dtype),
            conv.astype(dtype),
            # per-round SP2 dual-eval effort from the solve's device
            # counters (ROUND_COLS "sp2_evals"): attribution for the
            # warm-start claim — re-allocation rounds should spend fewer
            # evals than a cold solve
            counters[_COUNTER_COLS.index("sp2_evals")],
        ])
        return (state, shadow, qw, qu), (row, code, g.astype(dtype), s)

    q0 = jnp.zeros((K,), dtype)
    (state, _, _, _), (ledger, codes, gains, res) = lax.scan(
        step, (state0, shadow0, q0, q0), keys)
    return state, ledger, codes, gains, res


@partial(jax.jit, static_argnames=("acc", "cfg", "kernel"))
def _run_rounds_impl(sys, warr, acc, key, state0, cfg, kernel):
    return _cell_engine(sys, warr, acc, key, state0, cfg, kernel)


@partial(jax.jit, static_argnames=("acc", "cfg", "kernel"))
def _run_rounds_fleet_impl(sys_batch, warr, acc, keys, init_state, cfg,
                           kernel):
    """warr is the (C, 3) per-cell weights stack — a traced vmapped operand,
    so mixed per-cell weights share this one jit cache entry."""
    if init_state is None:
        def one(sysc, warr_c, kc):
            st = _init_carry_state(sysc, initial_allocation(sysc))
            return _cell_engine(sysc, warr_c, acc, kc, st, cfg, kernel)
        return jax.vmap(one)(sys_batch, warr, keys)

    def one(sysc, warr_c, kc, st):
        return _cell_engine(sysc, warr_c, acc, kc, st, cfg, kernel)
    return jax.vmap(one)(sys_batch, warr, keys, init_state)


def _result(out) -> RoundsResult:
    state, ledger, codes, gains, res = out
    B, p, f, s, s_hat, T = state
    alloc = Allocation(bandwidth=B, power=p, freq=f, resolution=s,
                       s_relaxed=s_hat, T=T)
    return RoundsResult(allocation=alloc, ledger=ledger, staleness=codes,
                        gains=gains, resolutions=res, columns=ROUND_COLS)


def _check_simulation_init(cfg: RoundsConfig, init: Optional[Allocation]):
    """bcd_iters=0 never solves, so the straggler deadline comes entirely
    from the init's makespan T — without one, deadline=0 and every device
    would silently read as late every round."""
    if (cfg.bcd_iters == 0 and cfg.participation != "full"
            and (init is None or init.T is None)):
        raise ValueError(
            "run_rounds: bcd_iters=0 with a straggler participation model "
            f"({cfg.participation!r}) needs an init allocation carrying a "
            "makespan T (e.g. BCDResult.allocation from allocate)")


def run_rounds(key: jax.Array, sys: SystemParams, w: Weights,
               cfg: RoundsConfig,
               acc: Optional[AccuracyModel] = None,
               init: Optional[Allocation] = None) -> RoundsResult:
    """Deprecated shim: the single-cell round scan through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys, weights=w, rounds=cfg,
    key=key, init=init))``. With `cfg.bcd_iters == 0` the init is
    *simulated* unchanged each round (no re-allocation) and must carry a
    makespan `T` for the straggler deadline.
    """
    from repro.api import Problem, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("run_rounds",
                     "Problem(system, weights, rounds=cfg, key=key)")
    return solve(Problem(system=sys, weights=w, acc=acc, init=init,
                         rounds=cfg, key=key))


def run_rounds_fleet(key: jax.Array, sys_batch: SystemParams, w: Weights,
                     cfg: RoundsConfig,
                     acc: Optional[AccuracyModel] = None,
                     init: Optional[Allocation] = None) -> RoundsResult:
    """Deprecated shim: the fleet round scan through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys_batch, weights=w, rounds=cfg,
    key=key, init=init))``. Cell c consumes the c-th split of `key`, so
    results match per-cell `run_rounds` calls with those keys. Per-cell
    weights: pass a sequence of `Weights` as `Problem.weights`.
    """
    from repro.api import Problem, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("run_rounds_fleet",
                     "Problem(system=sys_batch, weights, rounds=cfg, "
                     "key=key)")
    return solve(Problem(system=sys_batch, weights=w, acc=acc, init=init,
                         rounds=cfg, key=key))
