"""Operations and bytes of a kernel call from its shapes, and the peaks.

`sp1_lambda_sum` evaluates Sigma_n lambda_n(T) for M candidate deadlines
over N devices (per cell; a vmapped call adds a leading cell axis C). Its
operations are counted from the closed form it computes, per (deadline,
device) pair, with each exp, log or sqrt counted as one operation:

    t_c = max(T - tt, tiny)                                        2
    two f-clipped candidates: s = sqrt(t_c F / q),
        lam = (rhok / max(s, tiny) - 2 alpha F^2) F / (2 q)    2 x 9
    two s-clipped candidates: f = q S^2 / t_c, lam = k3 f^3        2 x 4
    the both-interior candidate exp(0.4 log c - 0.2 log(q t_c)),
        lam = k3 f^3                                               9
    nan-guard and clip of the five nonzero candidates              5 x 4
    forward makespan error of the six candidates: f = clip(cbrt(lam / k3)),
        psi = 2 alpha f^2 + 2 lam q / f, s = clip(rhok / psi),
        |q s^2 / f - t_c|                                          6 x 19
    least error, threshold, least lambda among the ties        5 + 2 + 17
    unattainable-deadline select                                   2
    accumulation into the sum                                      1
                                                                 = 198
plus 3 per device (q_safe, alpha) independent of the deadline. Bytes are
the float32 reads of T (M), the coefficients (8), q and tt (N each) and the
write of the M sums, per cell. The count depends only on the shapes, so it
is the same whatever implements the kernel.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Sequence, Tuple

OPS_PER_PAIR = 198
OPS_PER_DEVICE = 3
N_CONSTS = 8
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def sp1_lambda_sum_cost(out_shape: Sequence[int],
                        operand_shapes: Sequence[Sequence[int]]
                        ) -> Tuple[float, float]:
    """(operations, bytes) of one `sp1_lambda_sum` call: output (..., M, 1),
    operands T (..., M, 1), coefficients (..., 1, 8), q and tt (..., 1, N)."""
    if len(operand_shapes) != 4 or len(out_shape) < 2:
        raise ValueError(f"not an sp1_lambda_sum call: out {out_shape}, "
                         f"operands {operand_shapes}")
    M, N = int(out_shape[-2]), int(operand_shapes[2][-1])
    C = math.prod(int(x) for x in out_shape[:-2])
    ops = C * (M * N * OPS_PER_PAIR + N * OPS_PER_DEVICE)
    nbytes = 4 * C * (M + N_CONSTS + 2 * N + M)
    return float(ops), float(nbytes)


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of one chip of this kind. A kind that is not
    in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(ops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
