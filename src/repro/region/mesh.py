"""Mesh layer: shard the cell axis of a stacked fleet across local devices.

The fleet path of `repro.solve` vmaps the jitted BCD across cells on ONE
device; a region is C cells x N devices where C x N is millions of clients,
so the cell axis must spread over a device mesh (`Problem.mesh`). Two
execution modes (`SolverSpec.lockstep`):

  * `lockstep=True`: pure jit with `NamedSharding`-placed inputs — GSPMD
    partitions the vmapped solve along `cells`. The BCD `lax.while_loop`
    condition becomes a cross-device all-reduce, so every shard iterates
    until the globally slowest cell converges.
  * `lockstep=False` (default on a multi-device mesh): the same vmapped
    solver wrapped in `shard_map`, making the while_loop condition
    *shard-local* — a shard stops as soon as its own cells converge. Cells
    are solved by exactly the same select-masked program either way (the
    vmapped while_loop freezes converged lanes), so per-cell results are
    bit-identical between modes; only wall-clock differs. This is the
    "shard_map only if the BCD while_loop forces it" carve-out: the
    lockstep all-reduce is precisely what it buys back.

CPU dev recipe: XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.accuracy import AccuracyModel
from repro.core.bcd import FleetResult, _fleet_cell_fn
from repro.core.types import Allocation, SystemParams, Weights

Array = jnp.ndarray


def region_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh over the local devices with axis name "cells" (the logical
    axis `sharding.partition.region_rules` maps onto it)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), axis_names=("cells",))


def cell_specs(tree):
    """PartitionSpec pytree sharding every leaf's leading (cell) axis,
    derived from `sharding.partition.region_rules` (cells -> mesh axis,
    device and deeper axes shard-local)."""
    from repro.sharding.partition import logical_to_spec, region_rules

    rules = region_rules()
    return jax.tree_util.tree_map(
        lambda x: logical_to_spec(
            ("cells",) + ("device",) * (jnp.ndim(x) - 1), rules), tree)


def place_cells(tree, mesh: Mesh):
    """device_put every leaf with its cell axis sharded over `mesh`."""
    def put(x):
        x = jnp.asarray(x)
        spec = P("cells", *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, tree)


def pad_cells(tree, c_pad: int):
    """Pad every leaf's leading (cell) axis to `c_pad` by replicating the
    last cell — mesh shards must divide the cell count. Replicated cells
    cost duplicate work on the last shard only; callers slice them off."""
    def pad(x):
        x = jnp.asarray(x)
        c = x.shape[0]
        if c == c_pad:
            return x
        reps = jnp.broadcast_to(x[-1:], (c_pad - c,) + x.shape[1:])
        return jnp.concatenate([x, reps], axis=0)
    return jax.tree_util.tree_map(pad, tree)


@dataclasses.dataclass
class RegionResult:
    """A sharded fleet solve plus per-shard convergence stats.

    `stats` is gathered host-side lazily, ONCE, on first access (one
    device->host transfer of a packed (4 + 4*D,) array): the serving hot
    path — which only slices allocations back out — never pays the
    blocking sync, while monitoring callers still get the summary for
    free. The trailing 4*D block is the per-shard `SolveCounters`
    aggregation (summed bcd_iters/sp1_evals/sp2_evals and max residual
    over each shard's contiguous cell block, pad cells excluded) — the
    per-shard attribution the SLO plane and multi-host monitoring need
    without a second sync."""
    fleet: FleetResult
    _stats_packed: Array     # (4,) or (4 + 4*D,) device array, _pack_stats
    _n_cells: int
    _mesh_devices: int
    _stats_cache: Optional[dict] = dataclasses.field(default=None,
                                                     repr=False)

    @property
    def stats(self) -> dict:
        if self._stats_cache is None:
            vals = np.asarray(self._stats_packed)
            stats = dict(
                cells=self._n_cells, mesh_devices=self._mesh_devices,
                converged_frac=float(vals[0]), iters_max=int(vals[1]),
                iters_mean=float(vals[2]), objective_mean=float(vals[3]))
            if vals.shape[0] > 4:   # per-shard counter block (D, 4)
                shard = vals[4:].reshape(-1, 4)
                stats.update(
                    shard_bcd_iters=[float(x) for x in shard[:, 0]],
                    shard_sp1_evals=[float(x) for x in shard[:, 1]],
                    shard_sp2_evals=[float(x) for x in shard[:, 2]],
                    shard_residual_max=[float(x) for x in shard[:, 3]],
                    bcd_iters_total=float(shard[:, 0].sum()),
                    sp1_evals_total=float(shard[:, 1].sum()),
                    sp2_evals_total=float(shard[:, 2].sum()),
                    residual_max=float(shard[:, 3].max()))
            self._stats_cache = stats
        return self._stats_cache

    # convenience passthroughs so RegionResult reads like a FleetResult
    @property
    def allocation(self) -> Allocation:
        return self.fleet.allocation

    @property
    def objective(self) -> Array:
        return self.fleet.objective

    @property
    def iters(self) -> Array:
        return self.fleet.iters

    @property
    def converged(self) -> Array:
        return self.fleet.converged


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp1_method",
                                   "sp2_method", "sp2_iters", "kernel",
                                   "mesh", "lockstep", "with_init"))
def _region_solve_impl(sys_batch, warr, init, tol, acc: AccuracyModel,
                       max_iters: int, sp1_method: str, sp2_method: str,
                       sp2_iters: int, kernel: str, mesh: Mesh,
                       lockstep: bool, with_init: bool):
    """warr is the (C, 3) per-cell weights stack — a traced, cell-sharded
    operand, so mixed per-cell weights share this one jit cache entry."""
    fn = _fleet_cell_fn(acc, max_iters, tol, sp1_method, sp2_method,
                        sp2_iters, kernel, with_init)
    vf = jax.vmap(fn)
    args = (sys_batch, warr, init) if with_init else (sys_batch, warr)
    if lockstep or mesh.devices.size == 1:
        return vf(*args)
    in_specs = tuple(cell_specs(a) for a in args)
    return jax.shard_map(vf, mesh=mesh, in_specs=in_specs,
                         out_specs=P("cells"), check_vma=False)(*args)


@partial(jax.jit, static_argnames=("acc", "max_iters", "sp2_method",
                                   "sp2_iters", "mesh", "lockstep"))
def _region_fixed_impl(sys_batch, warr, T_round, alloc0, tol,
                       acc: AccuracyModel, max_iters: int, sp2_method: str,
                       sp2_iters: int, mesh: Mesh, lockstep: bool):
    """Deadline-constrained sibling of `_region_solve_impl`: the vmapped
    `_fleet_fixed_cell_fn` under shard_map. The per-cell per-round deadline
    `T_round` (C,) is a traced, cell-sharded operand — heterogeneous
    budgets share this one jit cache entry."""
    from repro.core.bcd import _fleet_fixed_cell_fn

    fn = _fleet_fixed_cell_fn(acc, max_iters, tol, sp2_method, sp2_iters)
    vf = jax.vmap(fn)
    args = (sys_batch, warr, T_round, alloc0)
    if lockstep or mesh.devices.size == 1:
        return vf(*args)
    in_specs = tuple(cell_specs(a) for a in args)
    return jax.shard_map(vf, mesh=mesh, in_specs=in_specs,
                         out_specs=P("cells"), check_vma=False)(*args)


def _pack_stats(fleet: FleetResult, n_shards: int = 1) -> Array:
    """Region summary stats packed into ONE device array — (4,) base
    stats plus, when the fleet carries `SolveCounters`, a (n_shards, 4)
    per-shard aggregation flattened behind them. The single lazy host
    transfer happens in `RegionResult.stats`.

    Shard attribution mirrors the mesh layout: cells are sharded in
    contiguous blocks of ceil(C / n_shards) (the `place_cells`
    NamedSharding), so shard d's block is rows [d*B, (d+1)*B) of the
    zero-padded counter matrix — pad cells contribute nothing (their
    replicated work on the last shard is an artifact of padding, not
    attributable solver effort). Effort columns (bcd_iters, sp1_evals,
    sp2_evals) are nansum'd per shard; the residual column is nanmax'd
    (a NaN residual marks a 0-iteration lane). All eager device ops on
    the already-computed result — no new compiled solve shapes."""
    dtype = jnp.asarray(fleet.objective).dtype
    base = jnp.stack([
        jnp.mean(fleet.converged.astype(dtype)),
        jnp.max(fleet.iters).astype(dtype),
        jnp.mean(fleet.iters.astype(dtype)),
        jnp.nanmean(fleet.objective),
    ])
    if fleet.counters is None:
        return base
    ctr = jnp.asarray(fleet.counters.data, dtype)       # (C, 4)
    C = ctr.shape[0]
    D = max(int(n_shards), 1)
    block = -(-C // D)
    pad = jnp.zeros((block * D - C, ctr.shape[1]), dtype)
    per_shard = jnp.concatenate([ctr, pad]).reshape(D, block, -1)
    effort = jnp.nansum(per_shard[..., :3], axis=1)     # (D, 3)
    resid = jnp.nanmax(per_shard[..., 3], axis=1)       # (D,)
    return jnp.concatenate(
        [base, jnp.concatenate([effort, resid[:, None]], axis=1).ravel()])


def _slice_fleet(fleet: FleetResult, n_cells: int) -> FleetResult:
    from repro.core.bcd import SolveCounters

    if int(fleet.iters.shape[0]) == n_cells:
        return fleet
    cut = lambda x: x[:n_cells]
    counters = fleet.counters
    if counters is not None:
        counters = SolveCounters(data=cut(counters.data),
                                 columns=counters.columns)
    return FleetResult(
        allocation=jax.tree_util.tree_map(cut, fleet.allocation),
        objective=cut(fleet.objective), iters=cut(fleet.iters),
        converged=cut(fleet.converged), history=cut(fleet.history),
        columns=fleet.columns, counters=counters)


def allocate_region(sys_batch: SystemParams, w: Weights,
                    acc: Optional[AccuracyModel] = None,
                    mesh: Optional[Mesh] = None,
                    max_iters: int = 20, tol: float = 1e-6,
                    init: Optional[Allocation] = None,
                    sp2_iters: int = 30, sp2_method: str = "direct",
                    sp1_method: str = "sweep",
                    lockstep: bool = False) -> RegionResult:
    """Deprecated shim: mesh-sharded fleet solve through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys_batch, weights=w,
    mesh=mesh or region_mesh(), ...), SolverSpec(lockstep=...))``. Per-cell
    outputs are bit-identical to the single-device fleet path — sharding
    moves work, not math — and per-cell weights are a traced, cell-sharded
    operand (pass a sequence of `Weights` as `Problem.weights`).
    """
    from repro.api import Problem, SolverSpec, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("allocate_region",
                     "Problem(system=sys_batch, weights, mesh=mesh), "
                     "SolverSpec(lockstep=...)")
    return solve(Problem(system=sys_batch, weights=w, acc=acc, init=init,
                         mesh=mesh if mesh is not None else region_mesh()),
                 SolverSpec(max_iters=max_iters, tol=tol,
                            sp1_method=sp1_method, sp2_method=sp2_method,
                            sp2_iters=sp2_iters, lockstep=lockstep))


def run_rounds_region(key: jax.Array, sys_batch: SystemParams, w: Weights,
                      cfg, acc: Optional[AccuracyModel] = None,
                      init: Optional[Allocation] = None,
                      mesh: Optional[Mesh] = None,
                      lockstep: bool = False):
    """Deprecated shim: mesh-sharded round dynamics through `repro.solve`.

    Equivalent to ``solve(Problem(system=sys_batch, weights=w, rounds=cfg,
    key=key, mesh=mesh or region_mesh(), ...), SolverSpec(lockstep=...))``.
    Per-cell key splits match `run_rounds_fleet` (cell c consumes split c of
    `key`; replicated pad cells reuse the last real cell's key and are
    sliced off), so results agree with the single-device engine.
    """
    from repro.api import Problem, SolverSpec, solve
    from repro.api.solve import _warn_deprecated

    _warn_deprecated("run_rounds_region",
                     "Problem(system=sys_batch, weights, rounds=cfg, "
                     "key=key, mesh=mesh), SolverSpec(lockstep=...)")
    return solve(Problem(system=sys_batch, weights=w, acc=acc, init=init,
                         rounds=cfg, key=key,
                         mesh=mesh if mesh is not None else region_mesh()),
                 SolverSpec(lockstep=lockstep))


@partial(jax.jit, static_argnames=("acc", "cfg", "kernel", "mesh",
                                   "lockstep", "with_init"))
def _region_rounds_impl(sys_batch, warr, keys, init_state, acc, cfg,
                        kernel: str, mesh: Mesh, lockstep: bool,
                        with_init: bool):
    """warr is the (C, 3) per-cell weights stack (traced, cell-sharded)."""
    from repro.dynamics.engine import (_cell_engine, _init_carry_state,
                                       initial_allocation)

    def one(sysc, warr_c, kc, *st):
        st0 = st[0] if with_init else _init_carry_state(
            sysc, initial_allocation(sysc))
        return _cell_engine(sysc, warr_c, acc, kc, st0, cfg, kernel)

    vf = jax.vmap(one)
    args = (sys_batch, warr, keys) + ((init_state,) if with_init else ())
    if lockstep or mesh.devices.size == 1:
        return vf(*args)
    in_specs = tuple(cell_specs(a) for a in args)
    return jax.shard_map(vf, mesh=mesh, in_specs=in_specs,
                         out_specs=P("cells"), check_vma=False)(*args)
