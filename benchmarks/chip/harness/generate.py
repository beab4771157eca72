"""The one traffic generator: deployments and request streams from a seed.

Everything here reads numbers from a configuration file (the deployment)
and a traffic file (the mix); nothing is specific to one cell. The channel
and device draws copy the paper's Sec. VII-A model (arXiv:2211.08705) so
that a change to the program under test cannot move the yardstick:

    devices uniform in an `area_m` square around the base station,
    pathloss PL(d) = a + b log10(d_km) dB with d floored at `min_distance_m`,
    lognormal shadowing of `shadowing_db`, folded in as its mean
    E[10^(X/10)] = exp(sigma^2 / 2), sigma = shadowing_db ln(10) / 10,
    or, where the traffic drifts the channel from round to round, realized
    from a standard-normal state x as median gain x exp(sigma x),
    c_n ~ U[cycles_lo, cycles_hi], D_n and d_n fixed.

Draws of many cells are made on the device in one jitted call each. Sizes,
weights and arrival gaps are stratified (the same set for every seed, in a
seed-drawn order), so that two seeds differ in order and channel, not in
the amount of work.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def dbm_to_watt(dbm: float) -> float:
    return 10.0 ** (dbm / 10.0) * 1e-3


def prng_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from any non-negative seed (wider than 32 bits too)
    and a stream number, with no collision between seeds that agree in
    their low 32 bits."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def cell_scalars(cfg: dict, n_devices) -> Dict[str, np.ndarray]:
    """Per-cell scalars of the deployment, as float32 arrays shaped like
    `n_devices` (a count per cell). Bandwidth is either per device (a
    fleet scaled from the paper's 20 MHz per 50 devices) or per cell."""
    dev, fl, ch = cfg["device"], cfg["fl"], cfg["channel"]
    n = np.asarray(n_devices, np.float64)
    if "bandwidth_hz_per_device" in cfg:
        bw = cfg["bandwidth_hz_per_device"] * n
    else:
        bw = np.full(n.shape, float(cfg["bandwidth_hz_per_cell"]))
    const = dict(
        noise_psd=dbm_to_watt(ch["noise_psd_dbm_per_hz"]),
        p_min=dbm_to_watt(dev["p_min_dbm"]),
        p_max=dbm_to_watt(dev["p_max_dbm"]),
        f_min=float(dev["f_min_hz"]), f_max=float(dev["f_max_hz"]),
        kappa=float(dev["kappa"]), local_iters=float(fl["local_iters"]),
        global_rounds=float(fl["global_rounds"]),
        s_standard=float(fl["s_standard"]))
    out = {k: np.full(n.shape, v, np.float32) for k, v in const.items()}
    out["bandwidth_total"] = bw.astype(np.float32)
    return out


@partial(jax.jit, static_argnames=("shape", "channel", "device"))
def _draw_cells(key, shape, channel, device):
    """gain, cycles, samples, bits, shadowing state, all `shape` + (N,)."""
    area, pl_a, pl_b, d_min, shadow_db = channel
    c_lo, c_hi, samples, bits = device
    k_pos, k_cyc, k_shadow = jax.random.split(key, 3)
    pos = (jax.random.uniform(k_pos, shape + (2,)) - 0.5) * area
    dist = jnp.maximum(jnp.linalg.norm(pos, axis=-1), d_min)
    pl_db = pl_a + pl_b * jnp.log10(dist / 1000.0)
    sigma = shadow_db * math.log(10.0) / 10.0
    gain = 10.0 ** (-pl_db / 10.0) * math.exp(sigma ** 2 / 2.0)
    cycles = jax.random.uniform(k_cyc, shape, minval=c_lo, maxval=c_hi)
    x = jax.random.normal(k_shadow, shape)
    return dict(gain=gain, cycles=cycles,
                samples=jnp.full(shape, float(samples)),
                bits=jnp.full(shape, float(bits)), shadow=x)


def draw_cells(key, shape, cfg: dict) -> dict:
    """Device arrays of the per-device parameters for `shape` = (..., N)."""
    ch, dev = cfg["channel"], cfg["device"]
    return _draw_cells(
        key, tuple(int(s) for s in shape),
        (float(ch["area_m"]), float(ch["pathloss_db_at_1km"]),
         float(ch["pathloss_slope_db"]), float(ch["min_distance_m"]),
         float(ch["shadowing_db"])),
        (float(dev["cycles_lo"]), float(dev["cycles_hi"]),
         float(dev["samples"]), float(dev["upload_bits"])))


@partial(jax.jit, static_argnames=("steps", "shadow_db"))
def _shadow_walk(key, expected, x0, rho, steps, shadow_db):
    """`steps` AR(1) steps x' = rho x + sqrt(1 - rho^2) z of the
    standard-normal shadowing state, and the gains they realize:
    expected / E[10^(X/10)] * exp(sigma x), so that E[gain] = expected."""
    sigma = shadow_db * math.log(10.0) / 10.0
    base = expected / math.exp(sigma ** 2 / 2.0)

    def step(x, k):
        x = rho * x + jnp.sqrt(1.0 - rho ** 2) * jax.random.normal(
            k, x.shape, x.dtype)
        return x, base * jnp.exp(sigma * x)

    _, gains = jax.lax.scan(step, x0, jax.random.split(key, steps))
    return jnp.concatenate([(base * jnp.exp(sigma * x0))[None], gains])


def stratified(rng: np.random.Generator, k: int, lo: float,
               hi: float) -> np.ndarray:
    """k values spread evenly over [lo, hi], in a seed-drawn order."""
    return rng.permutation(lo + (hi - lo) * (np.arange(k) + 0.5) / k)


def weight_rows(rng: np.random.Generator, k: int, spec: dict) -> np.ndarray:
    """(k, 3) normalized (w1, w2, rho) rows. `pairs` x `rho` draws the
    paper's swept settings in equal shares; `w1` and `rho` ranges draw
    w1 ~ U[w1], w2 = 1 - w1, rho ~ U[rho]."""
    if "pairs" in spec:
        combos = [(w1, w2, r) for (w1, w2) in spec["pairs"]
                  for r in spec["rho"]]
        idx = rng.permutation(np.arange(k) % len(combos))
        rows = np.asarray([combos[i] for i in idx], np.float64)
    else:
        w1 = stratified(rng, k, *spec["w1"])
        rows = np.stack([w1, 1.0 - w1,
                         stratified(rng, k, *spec["rho"])], axis=1)
    s = rows[:, 0] + rows[:, 1]
    return (rows / s[:, None]).astype(np.float32)


# ---------------------------------------------------------------- fleets

@dataclasses.dataclass
class Fleet:
    """One re-plan's problem: (C, N) device arrays, (C,) per-cell scalars
    and (C, 3) normalized weights, as device and host copies."""
    arrays: dict        # gain, cycles, samples, bits: (C, N) device arrays
    scalars: dict       # per-cell scalars: (C,) float32 numpy
    weights: np.ndarray  # (C, 3) float32


def fleets(cfg: dict, traffic: dict, seed: int) -> List[Fleet]:
    """The problems a re-plan cell cycles through. Cold traffic draws
    `fleets` independent fleets; warm traffic draws one fleet and
    `rounds` - 1 AR(1) shadowing steps of it (the next FL rounds)."""
    C, N = int(cfg["cells"]), int(cfg["devices"])
    rng = host_rng(seed, 1)
    scalars = cell_scalars(cfg, np.full((C,), N))
    if traffic.get("warm"):
        d = draw_cells(prng_key(seed, 2), (C, N), cfg)
        gains = _shadow_walk(prng_key(seed, 3), d["gain"], d["shadow"],
                             float(traffic["drift_rho"]),
                             int(traffic["rounds"]) - 1,
                             float(cfg["channel"]["shadowing_db"]))
        w = weight_rows(rng, C, cfg["weights"])
        rest = {k: d[k] for k in ("cycles", "samples", "bits")}
        return [Fleet(dict(rest, gain=gains[r]), scalars, w)
                for r in range(gains.shape[0])]
    P = int(traffic["fleets"])
    d = draw_cells(prng_key(seed, 2), (P, C, N), cfg)
    return [Fleet({k: d[k][i] for k in ("gain", "cycles", "samples",
                                         "bits")},
                  scalars, weight_rows(rng, C, cfg["weights"]))
            for i in range(P)]


def visit_order(traffic: dict, n: int, length: int) -> np.ndarray:
    """Which problem each re-plan solves: cold traffic cycles 0..n-1,
    warm traffic walks 0..n-1 and back, so consecutive re-plans are always
    one round apart."""
    if traffic.get("warm"):
        period = np.concatenate([np.arange(n), np.arange(n - 2, 0, -1)])
    else:
        period = np.arange(n)
    return np.resize(period, length)


# ---------------------------------------------------------------- requests

@dataclasses.dataclass
class Request:
    """One allocation request of an open-loop stream."""
    due: float           # seconds after the window opens
    cell_id: int
    n: int
    arrays: dict         # gain, cycles, samples, bits: (n,) float32 numpy
    scalars: dict        # per-cell scalars: python floats
    weights: np.ndarray  # (3,) normalized


def arrival_times(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of an open-loop stream over a window of `seconds`: the
    same number of arrivals for every seed. Poisson gaps are the
    exponential quantiles at the stated rate, scaled to fill the window
    and taken in a seed-drawn order."""
    arr = traffic["arrivals"]
    rate = float(arr["rate_per_s"])
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    k = max(int(round(rate * seconds)), 1)
    rng = host_rng(seed, 4)
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k)
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    return np.cumsum(rng.permutation(gaps))


def requests(cfg: dict, traffic: dict, seed: int,
             seconds: float) -> List[Request]:
    """An open-loop request stream. Each cell id's pool size, weights,
    positions and cycles are fixed at its first draw, and its devices'
    shadowing is a standard-normal state realized in the gain.

    With `ids` "fresh" every request is a new cell id, the first and only
    request of its cell, so no request finds an earlier answer to start
    from. Otherwise ids are drawn from the configuration's population, and
    a repeat of an id is its next FL round: a warm re-request after one
    AR(1) step x' = rho x + sqrt(1 - rho^2) z of that state, rho being
    `repeat_drift_rho`."""
    pop = cfg["population"]
    lo, hi = int(pop["devices"][0]), int(pop["devices"][1])
    due = arrival_times(traffic, seed, seconds)
    K = due.shape[0]
    fresh = traffic.get("ids") == "fresh"
    P = K if fresh else int(pop["cells"])
    rng = host_rng(seed, 5)
    sizes = np.rint(stratified(rng, P, lo - 0.5, hi + 0.5)).astype(int)
    sizes = np.clip(sizes, lo, hi)
    w = weight_rows(rng, P, cfg["weights"])
    d = jax.device_get(draw_cells(prng_key(seed, 6), (P, hi), cfg))
    sigma = float(cfg["channel"]["shadowing_db"]) * math.log(10.0) / 10.0
    median = np.asarray(d["gain"], np.float64) / math.exp(sigma ** 2 / 2.0)
    scal = cell_scalars(cfg, sizes)
    if fresh:
        ids, rho, z = np.arange(K), 0.0, None
    else:
        ids = rng.integers(0, P, size=K)
        rho = float(traffic["repeat_drift_rho"])
        z = np.asarray(jax.random.normal(prng_key(seed, 7), (K, hi)),
                       np.float64)
    state = {}
    out = []
    for i, (t, c) in enumerate(zip(due, ids)):
        c, n = int(c), int(sizes[c])
        x = state.get(c)
        if x is None:
            x = np.asarray(d["shadow"][c, :n], np.float64)
        else:
            x = rho * x + math.sqrt(1.0 - rho ** 2) * z[i, :n]
        state[c] = x
        out.append(Request(
            due=float(t), cell_id=c, n=n,
            arrays=dict(gain=(median[c, :n] * np.exp(sigma * x))
                        .astype(np.float32),
                        cycles=d["cycles"][c, :n],
                        samples=d["samples"][c, :n],
                        bits=d["bits"][c, :n]),
            scalars={k: float(v[c]) for k, v in scal.items()},
            weights=w[c]))
    return out
