"""The chip benchmark's harness at a tiny size on the CPU: cells resolve
by name, traffic is a function of the seed, the timed path refuses to run
without a TPU, and BENCHMARK.json keeps to its format."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_cells import BENCH, ROOT, make_root

from harness import generate, spec  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    c = spec.resolve(cell)
    assert c.config["reference"] and callable(c.reference.solve)
    assert c.traffic["kind"] in ("replan", "serve")
    assert callable(c.runner.run) and callable(c.runner.end_to_end)
    assert set(c.limits) == {"obj_gap", "infeasible"}
    assert {m["name"] for m in c.per_layer} == set(c.readers)
    assert c.per_layer and any(m["name"] != "setup_s" for m in c.end_to_end)
    assert "setup_s" in {m["name"] for m in c.end_to_end}


def test_a_cell_defined_only_by_new_files_loads(tmp_path):
    root = make_root(tmp_path)
    for cell in ("tiny.cold", "tiny.warm", "tiny.serve"):
        c = spec.resolve(cell, root)
        assert c.config["name"] == f"{cell}.config"
        assert c.traffic["trace_seconds"] == 0.5
    with pytest.raises(KeyError):
        spec.resolve("fleet.c64n2048.cold", root)


def test_benchmark_json_keeps_to_its_format():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for p in DOC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = set()
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        names.add(c["name"])
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in DOC["end_to_end"]}
    assert "setup_s" in e2e
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        assert (BENCH / "layers" / f"{m['name']}.py").is_file()
    all_names = [x["name"] for x in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(all_names) == len(set(all_names))
    assert all(NAME.match(n) for n in CELLS + list(names))


def test_a_new_kind_of_traffic_brings_its_own_runner(tmp_path):
    """A traffic kind the harness has never seen is run by the file
    `runners/<kind>.py` that comes with it."""
    root = make_root(tmp_path)
    bench = root / "benchmarks" / "chip"
    (bench / "runners" / "probe.py").write_text(
        "def run(cell, seed, seconds, traced, t_process, devs):\n"
        "    return None\n\n"
        "def end_to_end(rec):\n"
        "    return {'setup_s': 1.5}\n")
    (bench / "traffic" / "probe.json").write_text(json.dumps(
        {"kind": "probe", "trace_seconds": 0.5}))
    (bench / "limits" / "tiny.probe.json").write_text(json.dumps(
        {"obj_gap": 1e-3, "infeasible": 1e-5}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append(dict(name="tiny.probe", config="tiny.cold.config",
                                 traffic="probe", chips=1, why="test"))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    c = spec.resolve("tiny.probe", root)
    assert c.runner.end_to_end(None) == {"setup_s": 1.5}
    assert spec.resolve("tiny.serve", root).runner.__name__.endswith("serve")


def test_a_repeat_is_one_ar1_step_of_its_cell():
    """A re-requested cell keeps its pool and its draws but its gains move
    by one AR(1) step of the shadowing state: log-gain steps of about
    sigma sqrt(2 (1 - rho)) for a state at rest."""
    cfg, tr = _region_cfg(), _traffic("poisson")
    out = generate.requests(cfg, tr, 3, 4.0)
    last, steps = {}, []
    for r in out:
        prev = last.get(r.cell_id)
        if prev is not None:
            assert prev.n == r.n
            assert np.array_equal(prev.arrays["cycles"], r.arrays["cycles"])
            steps.append(np.log(r.arrays["gain"] / prev.arrays["gain"]))
        last[r.cell_id] = r
    sigma = cfg["channel"]["shadowing_db"] * np.log(10.0) / 10.0
    rho = tr["repeat_drift_rho"]
    ratio = np.std(np.concatenate(steps)) / (sigma * np.sqrt(2 * (1 - rho)))
    assert len(steps) > 100 and 0.85 < ratio < 1.15


TINY_FLEET = dict(cells=3, devices=40)


def _fleet_cfg():
    cfg = json.loads((BENCH / "configs" / "fleet_c64_n2048.json").read_text())
    cfg.update(TINY_FLEET)
    return cfg


def _region_cfg():
    cfg = json.loads((BENCH / "configs" / "paper_region.json").read_text())
    cfg["population"] = {"cells": 16, "devices": [10, 30]}
    return cfg


def _traffic(name, **edit):
    tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    tr.update(edit)
    return tr


def _fleet_draw(traffic, seed):
    out = generate.fleets(_fleet_cfg(), traffic, seed)
    return np.concatenate([np.ravel(np.asarray(f.arrays[k])) for f in out
                           for k in ("gain", "cycles")]
                          + [f.weights.ravel() for f in out])


def _stream_draw(traffic, seed):
    out = generate.requests(_region_cfg(), traffic, seed, 2.0)
    return np.concatenate([np.asarray([r.due, r.cell_id, r.n]) for r in out]
                          + [r.arrays["gain"] for r in out])


@pytest.mark.parametrize("draw,traffic", [
    (_fleet_draw, _traffic("replan_cold", fleets=2)),
    (_fleet_draw, _traffic("replan_warm", rounds=3)),
    (_stream_draw, _traffic("poisson")),
    (_stream_draw, _traffic("poisson_cold", arrivals={
        "process": "poisson", "rate_per_s": 200.0})),
], ids=["replan_cold", "replan_warm", "poisson", "poisson_cold"])
def test_traffic_is_a_function_of_the_seed(draw, traffic):
    big = 2 ** 31 + 11
    a, b = draw(traffic, big), draw(traffic, big)
    c = draw(traffic, big + 2 ** 32)   # same low 32 bits
    assert np.array_equal(a, b)
    assert a.shape != c.shape or not np.array_equal(a, c)


def test_streams_share_their_sizes_and_gaps_across_seeds():
    """Seeds reorder the same work: the same pool sizes and arrival gaps."""
    tr = _traffic("poisson")
    s1 = generate.requests(_region_cfg(), tr, 1, 2.0)
    s2 = generate.requests(_region_cfg(), tr, 2, 2.0)
    g1 = np.sort(np.diff([0.0] + [r.due for r in s1]))
    g2 = np.sort(np.diff([0.0] + [r.due for r in s2]))
    assert len(s1) == len(s2) and np.allclose(g1, g2, rtol=1e-9, atol=1e-12)
    assert generate.visit_order(_traffic("replan_warm"), 4, 9).tolist() == [
        0, 1, 2, 3, 2, 1, 0, 1, 2]


def test_fresh_ids_make_every_request_a_first_request():
    """`ids` "fresh": no id repeats, and seeds draw the same pool sizes."""
    tr = _traffic("poisson_cold", arrivals={"process": "poisson",
                                            "rate_per_s": 200.0})
    s1 = generate.requests(_region_cfg(), tr, 1, 2.0)
    s2 = generate.requests(_region_cfg(), tr, 2, 2.0)
    assert len({r.cell_id for r in s1}) == len(s1) == 400
    assert sorted(r.n for r in s1) == sorted(r.n for r in s2)
    assert {r.n for r in s1} == set(range(10, 31))


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_timed_path_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(BENCH / "bench.py"), "--workload",
         "fleet.c64n2048.cold", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and not out.stdout.strip()


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own files only
    (no program) runs nothing."""
    for p in DOC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable] + DOC["command"][1:] + [
            "--workload", "fleet.c64n2048.cold", "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()
