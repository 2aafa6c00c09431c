"""Everything a run needs, found by name under ``bench/``.

* ``BENCHMARK.json`` (repository root): which metrics a cell reports;
* ``bench/workloads/<cell>.json``: the cell's configuration, chips,
  traffic mix, serving entry point and check sizes;
* ``bench/configs/<config>.json``: the network's layer table, target and
  assumptions;
* ``bench/traffic/<mix>.json``: the traffic mix (see ``traffic.py``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.

Adding a cell, a configuration, a mix or a metric adds files and an entry
in ``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _reports(metric: Dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_of_cell


def load_cell(name: str, bench: Dict = None, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its files and the metrics it reports."""
    bench = bench if bench is not None else _json(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    d = root / "bench"
    workload = _json(d / "workloads" / f"{name}.json")
    config = _json(d / "configs" / f"{entry['config']}.json")
    mix = _json(d / "traffic" / f"{entry['traffic']}.json")
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: workload file says {key}="
                             f"{workload[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, names)]
    return Cell(name, workload, config, mix, e2e, per_layer)


def metric_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str, bench_dir: Path = BENCH) -> Dict:
    """The published peaks of one chip of ``device_kind`` ``kind``."""
    table = _json(bench_dir / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def families(bench_dir: Path = BENCH) -> Dict:
    return _json(bench_dir / "families.json")
