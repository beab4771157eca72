"""The comparison that decides `correct`.

An answer is one cell's allocation (B, p, f, s per device). It is judged
against the plain reference's allocation of the same cell, both evaluated
here in float64 numpy by the paper's objective (eq. 12):

  obj_gap     (J(answer) - J(reference))+ / (w1 E + w2 T + rho A) of the
              reference, widest over the cells checked: how far the
              answer's objective lies above the reference's. Block
              coordinate descent stops at a stationary point that depends
              on its path, and on hard cells the program's stops below the
              reference's; a feasible answer below the reference is no
              worse than it, and reads 0. The denominator is the sum of
              the objective's terms' magnitudes, which never nears 0 the
              way J itself can.
  infeasible  the widest relative breach of a constraint: sum B over the
              budget, B < 0, p or f outside its box, s off the menu
              (as shares of the budget, p_max, f_max and the top resolution).

Each number has its limit in `benchmarks/chip/limits/<cell>.json`.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("obj_gap", "infeasible")


def _f64(x):
    return np.asarray(x, np.float64)


def terms(arrays: dict, active, scalars: dict, weights, accuracy: dict,
          alloc: dict):
    """(C,) energy E, time T and accuracy A of `alloc` (B, p, f, s)."""
    act = np.asarray(active, bool)
    g, c, D, d = (_f64(arrays[k]) for k in ("gain", "cycles", "samples",
                                              "bits"))
    sc = {k: _f64(v)[:, None] for k, v in scalars.items()}
    B, p, f, s = (_f64(alloc[k]) for k in ("B", "p", "f", "s"))
    (s0, s1), (a0, a1) = accuracy["resolutions"], accuracy["map"]
    slope = (a1 - a0) / (s1 - s0)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        r = B * np.log2(1.0 + g * p / (sc["noise_psd"] * B))
        tt = d / r
        q = sc["local_iters"] * c * D / sc["s_standard"] ** 2
        tc = q * s * s / f
        e = p * tt + sc["kappa"] * q * s * s * f * f
    Rg = sc["global_rounds"][:, 0]
    E = Rg * np.sum(np.where(act, e, 0.0), axis=1)
    T = Rg * np.max(np.where(act, tc + tt, -np.inf), axis=1)
    A = np.sum(np.where(act, slope * (s - s0) + a0, 0.0), axis=1)
    return E, T, A


def objective(E, T, A, weights):
    w = _f64(weights)
    return w[:, 0] * E + w[:, 1] * T - w[:, 2] * A


def infeasibility(active, scalars: dict, alloc: dict, menu) -> np.ndarray:
    act = np.asarray(active, bool)
    sc = {k: _f64(v)[:, None] for k, v in scalars.items()}
    B, p, f, s = (_f64(alloc[k]) for k in ("B", "p", "f", "s"))
    menu = _f64(menu)
    zero = np.zeros_like(B)
    over = (np.sum(np.where(act, B, 0.0), axis=1) - sc["bandwidth_total"][:, 0]) \
        / sc["bandwidth_total"][:, 0]
    per_dev = np.maximum.reduce([
        -B / sc["bandwidth_total"],
        (sc["p_min"] - p) / sc["p_max"], (p - sc["p_max"]) / sc["p_max"],
        (sc["f_min"] - f) / sc["f_max"], (f - sc["f_max"]) / sc["f_max"],
        np.min(np.abs(s[..., None] - menu), axis=-1) / menu[-1]])
    per_dev = np.where(act & np.isfinite(per_dev), per_dev,
                       np.where(act, np.inf, zero))
    worst = np.maximum(over, np.max(per_dev, axis=1))
    return np.where(np.isfinite(worst), np.maximum(worst, 0.0), np.inf)


def compare(problem: dict, answer: dict, reference: dict) -> dict:
    """Numbers compared for a batch of cells: `problem` holds arrays,
    active, scalars, weights, accuracy and menu; `answer` and `reference`
    hold B, p, f, s (C, N). Non-finite answers read as infinite."""
    args = (problem["arrays"], problem["active"], problem["scalars"],
            problem["weights"], problem["accuracy"])
    E, T, A = terms(*args, answer)
    Er, Tr, Ar = terms(*args, reference)
    J = objective(E, T, A, problem["weights"])
    Jr = objective(Er, Tr, Ar, problem["weights"])
    w = _f64(problem["weights"])
    scale = w[:, 0] * np.abs(Er) + w[:, 1] * np.abs(Tr) + w[:, 2] * np.abs(Ar)
    gap = np.maximum(J - Jr, 0.0) / scale
    gap = np.where(np.isfinite(gap), gap, np.inf)
    bad = infeasibility(problem["active"], problem["scalars"], answer,
                        problem["menu"])
    return dict(obj_gap=float(np.max(gap)), infeasible=float(np.max(bad)),
                worst_cell=int(np.argmax(gap)), cells=int(gap.shape[0]))


def verdict(numbers: dict, limits: dict) -> bool:
    """True when every number compared is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
