"""Profiling plane: programmatic XLA trace sessions + compiled-cost gauges.

Two tools, both opt-in (nothing here runs on the serving path):

  * `trace(logdir, label=...)` — a context manager around
    `jax.profiler.start_trace`/`stop_trace`, span-keyed: the session is
    wrapped in an `obs.span("profile", label=...)`; inside the session
    every `obs.span` writes a `repro.<name>` host event, so host work
    shows up under the program's span names. One session at a time —
    a nested `trace` is a no-op yielding ``None`` (JAX raises on double
    start; serving loops shouldn't). The session's wall time lands in
    the `profiler_trace_seconds{label=...}` gauge and each completed
    session bumps `profiler_traces`.

  * `record_cost(label, fn, *args, ...)` — AOT-lower `fn` for the given
    arguments (`jax.jit(fn).lower(...).compile()`) and record the XLA
    cost analysis (FLOPs, bytes accessed) as
    `xla_cost_flops{shape=label}` / `xla_cost_bytes{shape=label}` gauges,
    so BENCH artifacts track compute-per-shape across PRs. Lowering
    compiles a fresh program by design — call it from benches, never
    from the serving path (the serve-time zero-new-compiles guard in
    tests/test_slo.py covers the SLO/scrape plane, which never imports
    this module's lowering).

`Compiled.cost_analysis()` is backend-dependent: it may return a list of
per-computation dicts, a bare dict, or raise `NotImplementedError` on
backends without a cost model. `record_cost` normalizes all three (returns
``None`` — and records nothing — when no cost model is available); an
error from the compile itself raises.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Optional

from . import recorder as _rec
from .metrics import MetricsRegistry, REGISTRY

__all__ = ["trace", "record_cost", "compile_solve", "solve_cost"]

_TRACE_LOCK = threading.Lock()
_TRACE_ACTIVE = False


@contextlib.contextmanager
def trace(logdir: str, label: str = "trace",
          registry: Optional[MetricsRegistry] = None):
    """Profile the enclosed block into `logdir` (TensorBoard/perfetto
    format). Yields the logdir, or ``None`` when a session is already
    active (nested use degrades to a plain pass-through)."""
    global _TRACE_ACTIVE
    import jax

    with _TRACE_LOCK:
        if _TRACE_ACTIVE:
            nested = True
        else:
            _TRACE_ACTIVE = True
            nested = False
    if nested:
        yield None
        return
    reg = registry if registry is not None else REGISTRY
    try:
        with _rec.span("profile", label=label):
            jax.profiler.start_trace(logdir)
            t0 = time.monotonic()
            try:
                yield logdir
            finally:
                jax.profiler.stop_trace()
                dur = time.monotonic() - t0
                reg.gauge("profiler_trace_seconds", label=label).set(dur)
                reg.counter("profiler_traces").inc()
    finally:
        with _TRACE_LOCK:
            _TRACE_ACTIVE = False


def _normalize_cost(cost) -> Optional[Dict[str, float]]:
    """One flat {key: float} from whatever `cost_analysis()` returned."""
    if cost is None:
        return None
    if isinstance(cost, (list, tuple)):
        merged: Dict[str, float] = {}
        for entry in cost:
            if not isinstance(entry, dict):
                continue
            for k, v in entry.items():
                try:
                    merged[k] = merged.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    pass
        return merged or None
    if isinstance(cost, dict):
        out = {}
        for k, v in cost.items():
            try:
                out[k] = float(v)
            except (TypeError, ValueError):
                pass
        return out or None
    return None


def record_cost(label: str, fn, *args,
                registry: Optional[MetricsRegistry] = None,
                static_argnames=(), **kwargs) -> Optional[Dict[str, float]]:
    """AOT-compile `fn(*args, **kwargs)` and record its XLA cost analysis.

    Returns the normalized cost dict (always containing ``flops`` and
    ``bytes_accessed`` keys, 0.0 when the backend reports neither), or
    ``None`` when the backend has no cost model. A compile error raises.
    `fn` may also be an already-jitted function — it is lowered as-is."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnames=static_argnames)
    return _record_compiled(label, jitted.lower(*args, **kwargs).compile(),
                            registry)


def _record_compiled(label: str, compiled,
                     registry: Optional[MetricsRegistry]
                     ) -> Optional[Dict[str, float]]:
    try:
        cost = _normalize_cost(compiled.cost_analysis())
    except NotImplementedError:   # the backend reports no cost model
        return None
    if cost is None:
        return None
    flops = cost.get("flops", 0.0)
    nbytes = cost.get("bytes accessed", 0.0)
    out = dict(cost)
    out["flops"] = flops
    out["bytes_accessed"] = nbytes
    reg = registry if registry is not None else REGISTRY
    reg.gauge("xla_cost_flops", shape=label).set(flops)
    reg.gauge("xla_cost_bytes", shape=label).set(nbytes)
    return out


def compile_solve(problem, spec=None):
    """AOT-compile the program `solve(problem, spec)` runs, without running
    it. Returns ``(label, compiled)``, the label keyed
    ``solve.<topology>.C<cells>.N<devices>``. Single-cell and unsharded
    (C, N) fleet topologies only; mesh/rounds/assoc problems are out of
    scope — profile those with `trace`."""
    import jax
    import jax.numpy as jnp

    from repro.api.problem import weights_leaf
    from repro.api.solve import _apply_dtype, _topology_label
    from repro.api.spec import SolverSpec
    from repro.core.accuracy import default_accuracy
    from repro.core.bcd import (_allocate_impl, _fleet_cell_fn,
                                _init_carry_state, initial_allocation)
    from repro.kernels.ops import kernel_mode

    spec = SolverSpec() if spec is None else spec
    topo = _topology_label(problem)
    if topo not in ("bcd", "bcd_fleet"):
        raise ValueError(
            f"compile_solve: only single-cell and fleet topologies are "
            f"supported, got {topo!r}")
    sysp, init = _apply_dtype(problem.system, problem.init, spec.dtype)
    acc = problem.acc if problem.acc is not None else default_accuracy()
    gain = jnp.asarray(sysp.gain)
    if topo == "bcd":
        alloc0 = init if init is not None else initial_allocation(sysp)
        state0 = _init_carry_state(sysp, alloc0)
        warr = weights_leaf(problem.weights, state0[0].dtype)
        compiled = _allocate_impl.lower(
            sysp, warr, acc, state0, spec.max_iters, spec.tol,
            spec.sp1_method, spec.sp2_method, spec.sp2_iters,
            kernel_mode()).compile()
        return f"solve.bcd.N{gain.shape[0]}", compiled
    C, N = int(gain.shape[0]), int(gain.shape[1])
    warr = weights_leaf(problem.weights, gain.dtype, cells=C)
    fn = _fleet_cell_fn(acc, spec.max_iters, spec.tol, spec.sp1_method,
                        spec.sp2_method, spec.sp2_iters, kernel_mode(),
                        with_init=init is not None)
    args = (sysp, warr) if init is None else (sysp, warr, init)
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    return f"solve.fleet.C{C}.N{N}", compiled


def solve_cost(problem, spec=None,
               registry: Optional[MetricsRegistry] = None
               ) -> Optional[Dict[str, float]]:
    """Cost analysis for the compiled program `solve(problem, spec)` would
    run (see `compile_solve` for the label and the topologies it covers).
    Never executes the solve; ``None`` when the backend has no cost
    model."""
    label, compiled = compile_solve(problem, spec)
    return _record_compiled(label, compiled, registry)
