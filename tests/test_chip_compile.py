"""Compile-only guards for the TPU: the SP1 sweep kernel and the solves
that carry it, compiled by the TPU compiler for a described (not attached)
v5e chip at the widths the solver feeds it.

Interpret mode cannot catch what Mosaic refuses (unlowerable primitives,
block shapes off the (8, 128) tiling under `vmap`); these tests can,
without a chip. Nothing runs, so they say nothing about results or times.
Each compile runs with x64 off, as the chip path does (`chip_smoke.py`).
The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import Weights, make_fleet
from repro.api.problem import weights_leaf
from repro.core.accuracy import default_accuracy
from repro.core.bcd import _fleet_solve_impl
from repro.core.sp1 import _SWEEP_POINTS
from repro.kernels import ops
from repro.kernels.sp1_sweep import N_CONSTS

SPEC_ARGS = dict(max_iters=8, tol=1e-4)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off:
    entries compiled for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding_of):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding_of(jnp.ndim(x))),
        tree)


@pytest.mark.parametrize("cells,n", [(None, 2048), (64, 2048), (16, 300)])
def test_sp1_kernel_compiles_for_v5e(one_chip, cells, n):
    """Single cell at N=2048 (two lane blocks), the C64 fleet vmap, and the
    16 cells one chip holds in the four-chip region at a padded N=300."""
    def sweep(T, q, tt, c):
        return ops.sp1_lambda_sum(T, q, tt, c, impl="mosaic")

    lead = () if cells is None else (cells,)
    args = [jax.ShapeDtypeStruct(lead + (m,), jnp.float32,
                                 sharding=one_chip)
            for m in (_SWEEP_POINTS, n, n, N_CONSTS)]
    fn = sweep if cells is None else jax.vmap(sweep)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fleet(cells, n):
    fleet = make_fleet(jax.random.PRNGKey(0), n_cells=cells, n_devices=n,
                       bandwidth_total=20e6 * n / 50)
    return fleet, weights_leaf(Weights(0.5, 0.5, 1.0), jnp.float32,
                               cells=cells)


def test_fleet_solve_compiles_for_v5e(one_chip):
    """The whole C64 x N2048 fleet solve `solve()` runs, with the kernel
    compiled by Mosaic inside it, fits one chip."""
    with jax.enable_x64(False):
        sysb, warrb = _shapes(_fleet(64, 2048), lambda nd: one_chip)
        tol = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        compiled = _fleet_solve_impl.lower(
            sysb, warrb, None, tol, default_accuracy(),
            SPEC_ARGS["max_iters"], "sweep", "direct", 30, "mosaic",
            False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_region_solve_compiles_for_four_chips(topo):
    """The C64 x N2048 region solve, shard_map over a 4-chip `cells` mesh:
    each chip holds about a quarter of the arguments (a replicated copy
    would hold all of them) and no collective is needed (cells are
    independent)."""
    from repro.region.mesh import _region_solve_impl

    mesh = Mesh(np.array(topo.devices), axis_names=("cells",))
    with jax.enable_x64(False):
        fleet, warr = _fleet(64, 2048)
        sysb, warrb = _shapes((fleet, warr), lambda nd: NamedSharding(
            mesh, P("cells", *([None] * (nd - 1)))))
        tol = jax.ShapeDtypeStruct((), jnp.float32,
                                   sharding=NamedSharding(mesh, P()))
        compiled = _region_solve_impl.lower(
            sysb, warrb, None, tol, default_accuracy(),
            SPEC_ARGS["max_iters"], "sweep", "direct", 30, "mosaic", mesh,
            False, False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text
    full = sum(np.asarray(x).nbytes for x in
               jax.tree_util.tree_leaves((fleet, warr)))
    assert compiled.memory_analysis().argument_size_in_bytes < full / 2
