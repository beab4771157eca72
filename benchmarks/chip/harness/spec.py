"""Find a cell's parts by name.

`BENCHMARK.json` names each cell's configuration and traffic mix. The
harness then reads, with no list of its own:

    the configuration file     the `file` of the cell's config entry
    benchmarks/chip/traffic/<traffic>.json
    benchmarks/chip/limits/<cell>.json      the limits of `correct`
    benchmarks/chip/runners/<kind>.py       the runner of the traffic's
                                            `kind`: `run` drives the
                                            window, `end_to_end` reduces it
    benchmarks/chip/layers/<metric>.py      one reader per per-layer metric,
                                            `read(run) -> float | None`
    benchmarks/chip/reference/<name>.py     the plain reference the
                                            configuration names

so a cell, a configuration, a kind of traffic or a per-layer metric is
added by adding files and entries, never by editing a file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[3]
BENCH = Path("benchmarks") / "chip"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]
    reference: object
    runner: object


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """Every part of one cell, found by the names in `BENCHMARK.json`."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    base = root / BENCH
    traffic = json.loads((base / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((base / "limits" / f"{workload}.json").read_text())
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: _module(base / "layers" / f"{m['name']}.py",
                                  "bench_layer_" + re.sub(r"\W", "_",
                                                          m["name"])).read
               for m in per_layer}
    reference = _module(base / "reference" / f"{config['reference']}.py",
                        f"bench_reference_{config['reference']}")
    runner = _module(base / "runners" / f"{traffic['kind']}.py",
                     f"bench_runner_{traffic['kind']}")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=per_layer, readers=readers, reference=reference,
                runner=runner)
