"""`correct` at a tiny size on the CPU: sound runs pass, and the control
(the reference one precision lower in the program's place) and every fault
the cells can have are caught.

The faults are planted under the harness, in the program's timed path:
a re-plan that hands back the state it started from, half of the fleet
left at its start, the cells of every shard but the first left at their
start (the gather across chips left out), an answer altered where it is
produced, and a serving cache that hands a re-requested cell its last
answer back without solving again."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import LIMITS, make_root, run

import bench  # noqa: E402
from harness import program, spec  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", ["tiny.cold", "tiny.warm", "tiny.serve"])
def test_sound_runs_are_correct(root, cell, capsys):
    line = run(root, cell, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared"
    for k, v in line["compared"].items():
        assert v["value"] <= v["limit"] == LIMITS[k]
    names = {"tiny.serve": {"setup_s", "alloc_p95_ms", "allocs_per_s"}}
    assert set(line["metrics"]) == names.get(cell, {"setup_s", "replan_ms"})


@pytest.mark.parametrize("cell", ["tiny.cold", "tiny.serve"])
def test_control_fails_the_limits(root, cell):
    """The plain reference in bfloat16 (one below the configuration's
    float32) put in the program's place reads over a limit."""
    c = spec.resolve(cell, root)
    with jax.enable_x64(False):
        rec = c.runner.run(c, 7, 0.5, False, 0.0, jax.devices()[:1])
        by32, _ = bench.reference(c, rec.checks)
        by16, _ = bench.reference(c, rec.checks, dtype="bfloat16")
    ok = bench.compare(rec.checks, by32)
    ctrl = bench.compare([(k, p, by16[k]) for k, p, _ in rec.checks], by32)
    assert all(ok[k] <= LIMITS[k] for k in LIMITS)
    assert any(ctrl[k] > LIMITS[k] for k in LIMITS)


def _start(sys_, init):
    """The allocation a re-plan starts from: its init, or the program's
    cold start (B / N each, p_max)."""
    if init is not None:
        return init
    from repro.core.bcd import initial_allocation

    return jax.vmap(initial_allocation)(sys_)


def _mix(res, start, keep):
    """`res` with the cells where `keep` is False set back to `start`."""
    a, s = res.allocation, start
    pick = lambda x, y: jnp.where(keep[:, None], x, jnp.asarray(y, x.dtype))
    return dataclasses.replace(res, allocation=dataclasses.replace(
        a, bandwidth=pick(a.bandwidth, s.bandwidth),
        power=pick(a.power, s.power), freq=pick(a.freq, s.freq),
        resolution=pick(a.resolution, s.resolution)))


def _fault(kind):
    real = program.solve

    def solve(sys_, w, spec_, acc, init=None, mesh_=None):
        res = real(sys_, w, spec_, acc, init=init, mesh_=mesh_)
        C = res.objective.shape[0]
        cells = jnp.arange(C)
        if kind == "answer_altered":
            bw = res.allocation.bandwidth.at[:, 0].multiply(1.5)
            return dataclasses.replace(res, allocation=dataclasses.replace(
                res.allocation, bandwidth=bw))
        keep = {"state_unchanged": cells < 0,
                "half_batch": cells < C // 2,
                "exchange_left_out": cells < C // 4}[kind]
        return _mix(res, _start(sys_, init), keep)

    return solve


@pytest.mark.parametrize("cell", ["tiny.cold", "tiny.warm"])
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "exchange_left_out", "answer_altered"])
def test_replan_faults_are_caught(root, cell, kind, capsys, monkeypatch):
    monkeypatch.setattr(program, "solve", _fault(kind))
    line = run(root, cell, capsys, seconds=0.5)
    assert line["correct"] is False


def _serve_fault(kind):
    import repro.region.dispatch as dispatch

    real = dispatch.solve

    def solve(problem, spec_):
        res = real(problem, spec_)
        C = res.objective.shape[0]
        if kind == "answer_altered":
            bw = res.allocation.bandwidth.at[:, 0].multiply(1.5)
            return dataclasses.replace(res, allocation=dataclasses.replace(
                res.allocation, bandwidth=bw))
        keep = {"state_unchanged": jnp.arange(C) < 0,
                "half_batch": jnp.arange(C) % 2 == 0}[kind]
        return _mix(res, problem.init, keep)

    return dispatch, solve


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_serve_faults_are_caught(root, kind, capsys, monkeypatch):
    dispatch, solve = _serve_fault(kind)
    monkeypatch.setattr(dispatch, "solve", solve)
    line = run(root, "tiny.serve", capsys, seconds=1.0)
    assert line["correct"] is False


def test_serve_stale_warm_answers_are_caught(root, monkeypatch):
    """Only the warm lanes of each batch (a re-request of a cell the
    pipeline has answered) get the cached start handed back unsolved; the
    cold lanes are solved as they should be. The checked sample holds both
    kinds, and the stale warm answers read over a limit."""
    from repro.region.dispatch import Dispatcher

    real = Dispatcher.dispatch

    def dispatch(self, plan):
        batch = real(self, plan)
        warm = np.zeros(batch.result.objective.shape[0], bool)
        warm[:plan.n_real] = plan.warm
        return dataclasses.replace(batch, result=_mix(
            batch.result, plan.init_batch, jnp.asarray(~warm)))

    c = spec.resolve("tiny.serve", root)
    with jax.enable_x64(False):
        rec = c.runner.run(c, 11, 2.0, False, 0.0, jax.devices()[:1])
        sound = bench.compare(rec.checks, bench.reference(c, rec.checks)[0])
        monkeypatch.setattr(Dispatcher, "dispatch", dispatch)
        rec = c.runner.run(c, 11, 2.0, False, 0.0, jax.devices()[:1])
        stale = bench.compare(rec.checks, bench.reference(c, rec.checks)[0])
    warm = np.concatenate([p["init"]["warm"] for _, p, _ in rec.checks])
    assert 0 < warm.sum() < warm.size
    assert all(sound[k] <= LIMITS[k] for k in LIMITS)
    assert any(stale[k] > LIMITS[k] for k in LIMITS)


# the readings each committed limit was set from, on the chip (PERF.md):
# (largest reading of a sound run, smallest control reading)
READINGS = {
    "fleet.c64n2048.cold": {"obj_gap": (1.497e-3, 2.770),
                            "infeasible": (1.513e-7, 1.275e-3)},
    "metro.c256n2048.mesh4": {"obj_gap": (1.503e-3, 2.917),
                              "infeasible": (1.316e-7, 1.275e-3)},
    "region.paper.cold": {"obj_gap": (1.748e-2, 6.626e-2),
                          "infeasible": (1.395e-7, 6.387e-3)},
}


@pytest.mark.parametrize("cell", sorted(READINGS))
def test_limits_sit_between_the_readings(cell):
    """Each committed limit lies between the program's largest reading and
    the control's smallest, which is three times the first or more, with
    room on both sides."""
    lim = spec.resolve(cell).limits
    for k, (lower, upper) in READINGS[cell].items():
        assert upper >= 3 * lower
        assert lim[k] / lower >= 2 and upper / lim[k] >= 1.5
