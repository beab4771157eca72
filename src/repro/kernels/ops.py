"""Jit'd public wrappers for the Pallas kernels.

How a kernel runs is one static *mode*, resolved by `kernel_mode()` outside
any jit and handed down as a static argument, so it is part of the jit key
of every program that reaches the kernel:

  "mosaic"     compiled Pallas — the default on a TPU backend;
  "interpret"  the kernel body run as traced JAX ops — only when asked for,
               by REPRO_FORCE_INTERPRET=1 or impl="interpret";
  "ref"        the pure-jnp oracle — the default on any other backend (the
               CPU test path), or when asked for by impl="ref".

The LLM-stack kernels (flash attention, Mamba and RWKV-6 scans) have no ref
form: under "ref" they run their kernel body in interpret mode.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.ref import sp1_lambda_sum_ref as _sp1_sweep_ref
from repro.kernels.ref import waterfill_gprime_ref as _waterfill_ref
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv
from repro.kernels.sp1_sweep import sp1_lambda_sum as _sp1_sweep
from repro.kernels.waterfill import waterfill_gprime as _waterfill

KERNEL_MODES = ("mosaic", "interpret", "ref")


def kernel_mode(impl: str = "auto") -> str:
    """Resolve `impl` to one of `KERNEL_MODES`. "auto" is compiled Pallas
    on a TPU and the ref oracle elsewhere; REPRO_FORCE_INTERPRET=1 turns
    "auto" into interpret mode on any backend. An explicit mode is returned
    as is: "mosaic" off a TPU fails when the kernel lowers, it is never
    swapped for another mode. Call it outside jit and pass the result down
    as a static argument."""
    if impl in KERNEL_MODES:
        return impl
    if impl != "auto":
        raise ValueError(
            f"impl must be auto or one of {KERNEL_MODES}, got {impl!r}")
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return "interpret"
    return "mosaic" if jax.default_backend() == "tpu" else "ref"


def _interpret(mode: str) -> bool:
    return mode != "mosaic"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def _flash_dispatch(q, k, v, *, causal, window, block_q, block_k, interpret):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128):
    return _flash_dispatch(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=_interpret(kernel_mode()))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _rwkv_dispatch(r, k, v, logw, u, *, chunk, interpret):
    return _rwkv(r, k, v, logw, u, chunk=chunk, interpret=interpret)


def rwkv6_scan(r, k, v, logw, u, *, chunk: int = 64):
    return _rwkv_dispatch(r, k, v, logw, u, chunk=chunk,
                          interpret=_interpret(kernel_mode()))


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def _mamba_dispatch(dt, A, Bt, Ct, x, *, chunk, block_d, interpret):
    return _mamba(dt, A, Bt, Ct, x, chunk=chunk, block_d=block_d,
                  interpret=interpret)


def mamba_scan(dt, A, Bt, Ct, x, *, chunk: int = 64, block_d: int = 256):
    return _mamba_dispatch(dt, A, Bt, Ct, x, chunk=chunk, block_d=block_d,
                           interpret=_interpret(kernel_mode()))


def waterfill_compute_dtype(input_dtype):
    """Dtype the dual sweep actually computes in: f32 on TPU (no f64 on the
    VPU, and interpret mode still lowers through TPU XLA), the input dtype
    elsewhere. Callers sizing search brackets (core.sp2._thm2_dual_mu) must
    respect this, not the input dtype — an f64-sized bracket overflows the
    f32 kernel to NaN."""
    if jax.default_backend() == "tpu":
        return jnp.dtype(jnp.float32)
    return jnp.dtype(input_dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "mode", "dtype"))
def _waterfill_dispatch(mu, j, rmin, B_total, *, block_n: int,
                        mode: str, dtype):
    if mode == "ref":
        return _waterfill_ref(mu.astype(dtype), j.astype(dtype),
                              rmin.astype(dtype), jnp.asarray(B_total, dtype))
    return _waterfill(mu, j, rmin, jnp.asarray(B_total, dtype),
                      block_n=block_n, interpret=_interpret(mode),
                      dtype=dtype)


def waterfill_gprime(mu, j, rmin, B_total, *, block_n: int = 1024,
                     impl: str = "auto"):
    """Entry for the SP2 dual sweep (used by `core.sp2`'s thm2 reference).

    impl: "auto" or a mode of `KERNEL_MODES` (see `kernel_mode`).
    B_total may be a traced scalar (a per-cell leaf in heterogeneous fleets).
    Computes in `waterfill_compute_dtype(mu.dtype)`.
    """
    return _waterfill_dispatch(mu, j, rmin, B_total, block_n=block_n,
                               mode=kernel_mode(impl),
                               dtype=waterfill_compute_dtype(mu.dtype))


@functools.partial(jax.jit, static_argnames=("block_n", "mode", "dtype"))
def _sp1_sweep_dispatch(T_grid, q, tt, consts, *, block_n: int,
                        mode: str, dtype):
    if mode == "ref":
        return _sp1_sweep_ref(T_grid.astype(dtype), q.astype(dtype),
                              tt.astype(dtype), consts.astype(dtype))
    return _sp1_sweep(T_grid, q, tt, consts, block_n=block_n,
                      interpret=_interpret(mode), dtype=dtype)


def sp1_lambda_sum(T_grid, q, tt, consts, *, block_n: int = 1024,
                   impl: str = "auto"):
    """Production entry for the batched SP1 dual sweep (used by `core.sp1`):
    Sigma_n lambda_n(T) for M candidate deadlines in one device pass.

    T_grid: (M,) candidate round deadlines; q/tt: (N,) per-device cycle and
    transmission-time coefficients; consts: (sp1_sweep.N_CONSTS,) scalar
    coefficient vector (may be traced — per-cell leaves vary across a
    heterogeneous fleet). impl: "auto" or a mode of `KERNEL_MODES`; the
    solver passes the mode it resolved outside its own jit. Computes in
    `waterfill_compute_dtype(T_grid.dtype)`.
    """
    return _sp1_sweep_dispatch(T_grid, q, tt, consts, block_n=block_n,
                               mode=kernel_mode(impl),
                               dtype=waterfill_compute_dtype(T_grid.dtype))
