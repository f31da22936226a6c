"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each metric has a reader of its own. All are found by name, so a new cell,
configuration, traffic mix or metric is new files plus entries in
``BENCHMARK.json``, never an edit to a file that is already here:

    chipbench/configs/<config>.json    deployment: schema, scale, system spec
    chipbench/traffic/<traffic>.json   mix: write share, group size, rate
    chipbench/metrics/<metric>.py      reader: ``read(run) -> float | None``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    # (metric entry of BENCHMARK.json, reader) for each metric this cell
    # reports, by kind: "end_to_end" (--trace 0) or "per_layer" (--trace 1)
    metrics: dict[str, list[tuple[dict, object]]]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, root: Path):
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, bench_path: Path) -> Cell:
    """The cell ``name`` of the benchmark file, with its configuration,
    traffic and metric readers loaded from the ``chipbench/`` beside it."""
    bench = load_json(bench_path)
    root = bench_path.parent / HERE.name
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(bench_path.parent / configs[w["config"]]["file"])
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = [(m, load_reader(m["name"], root))
                         for m in bench[kind]
                         if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics=metrics)
