"""Tiny cells for the benchmark's CPU tests, defined only by files in a
temporary directory, the way a later change adds a cell."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    # cell: (config it shrinks, its sizes, traffic it shrinks, traffic edits)
    "tiny.cold": ("fleet_c64_n2048", dict(cells=4, devices=64),
                  "replan_cold", dict(fleets=2)),
    "tiny.warm": ("fleet_c64_n2048", dict(cells=4, devices=64),
                  "replan_warm", dict(rounds=3)),
    "tiny.serve": ("paper_region",
                   dict(population={"cells": 32, "devices": [10, 40]},
                        pipeline={"cells_per_batch": 4, "min_bucket": 16,
                                  "max_wait_s": 0.05, "max_in_flight": 2}),
                   "poisson", dict(arrivals={"process": "poisson",
                                             "rate_per_s": 40.0},
                                   check_requests=16)),
}
LIMITS = {"obj_gap": 1e-3, "infeasible": 1e-5}


def make_root(tmp: Path) -> Path:
    """A checkout-shaped directory whose BENCHMARK.json holds only tiny
    cells, each a new config, traffic and limits file beside copies of the
    benchmark's readers, runners and reference."""
    bench = tmp / "benchmarks" / "chip"
    for sub in ("layers", "reference", "runners"):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc = dict(real, configs=[], workloads=[])
    for cell, (cfg_name, sizes, tr_name, tr_edit) in TINY.items():
        cfg = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
        cfg.update(sizes, name=f"{cell}.config")
        cfg_file = bench / "configs" / f"{cell}.json"
        cfg_file.write_text(json.dumps(cfg))
        tr = json.loads((BENCH / "traffic" / f"{tr_name}.json").read_text())
        tr.update(tr_edit, trace_seconds=0.5)
        (bench / "traffic" / f"{cell}.json").write_text(json.dumps(tr))
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
        doc["configs"].append(dict(
            name=f"{cell}.config", source=cfg["source"],
            file=str(cfg_file.relative_to(tmp)), reduced=["cells"], why="test"))
        doc["workloads"].append(dict(name=cell, config=f"{cell}.config",
                                     traffic=cell, chips=1, why="test"))
    kinds = {"replan_ms": ["tiny.cold", "tiny.warm"],
             "alloc_p95_ms": ["tiny.serve"], "allocs_per_s": ["tiny.serve"]}
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = kinds[m["name"]]
    for m in doc["per_layer"]:
        m["workloads"] = (["tiny.serve"] if m["name"].endswith(".serve")
                          else ["tiny.cold", "tiny.warm"])
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp


def cpu_devices(chips: int):
    import jax

    return jax.devices()[:chips]


def run(root: Path, cell: str, capsys, seed: int = 2 ** 31 + 5,
        seconds: float = 1.0, trace: int = 0) -> dict:
    """One run of the harness on the CPU, with the accelerator check and
    the persistent compile cache stepped round; returns its result line."""
    import jax

    import bench
    from harness import device

    keep = device.use_compile_cache
    device.use_compile_cache = lambda: "off"
    try:
        with jax.enable_x64(False):
            rc = bench.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace)], require=cpu_devices, root=root)
    finally:
        device.use_compile_cache = keep
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
