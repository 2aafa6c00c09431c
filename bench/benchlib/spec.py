"""Everything a run needs, found by name under ``bench/``.

* ``BENCHMARK.json`` (repository root): which metrics a cell reports;
* ``bench/workloads/<cell>.json``: the cell's configuration, chips,
  traffic mix, serving entry point and check sizes;
* ``bench/configs/<config>.json``: the network's layer table, target and
  assumptions;
* ``bench/traffic/<mix>.json``: the traffic mix (see ``traffic.py``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``;
* ``bench/references/<config>.py``: a configuration's own plain
  reference, where the shared one cannot express its layers.

A configuration file's ``layers`` rows begin with nine columns, ``[name,
kind, k_h, k_w, c_in, c_out, stride, in_h, in_w]``.  The work counts
(``work.layers_of``) and the program (``ConvLayerSpec``, through
``run.py::program_config``) read these nine and no more; a row may carry
trailing columns (an activation, a block, a join) that only the
configuration's own reference reads.  A field added to ``ConvLayerSpec``
later is not fed from them by position: ``program_config`` names the
column it reads.  A further file and key bring a configuration's own layer
semantics:

* ``bench/references/<config>.py``, found by the configuration's
  ``name``, defines ``logits_in_blocks(params, layers, images, *,
  act_scale, block, weight_bits=8)`` and may define ``make_params(key,
  layers, act_scale)`` (default ``model.make_params``) and
  ``layers_of(table)`` (default ``work.layers_of``; its items offer what
  ``work.Layer`` offers and may offer a ``family``).  Without that file
  the configuration takes ``benchlib/reference.py`` and both defaults.
* ``"families": {"<family>": ["<HLO base-name prefix>", ...]}`` in the
  configuration file adds kernel families to ``families.json`` for this
  configuration's cells, so a new kernel's roofline is one more reader in
  ``bench/metrics/``.

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


def _load(path: Path, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    return _load(path, "bench_metric_" + name.replace(".", "_")
                 .replace("-", "_")).read


def peaks(kind: str, bench_dir: Path = BENCH) -> Dict:
    """The published peaks of one chip of ``device_kind`` ``kind``."""
    table = _json(bench_dir / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


@dataclass(frozen=True)
class Reference:
    """A configuration's plain reference and the work counts it implies."""
    path: Path
    logits_in_blocks: Callable
    make_params: Callable
    layers_of: Callable


def reference_of(conf: Dict, bench_dir: Path = BENCH) -> Reference:
    """The reference of the configuration ``conf``:
    ``bench/references/<name>.py`` where the configuration has one, else
    the shared ``benchlib/reference.py``; what the module leaves out is
    taken from ``model.make_params`` and ``work.layers_of``."""
    from benchlib import model, reference, work
    name = conf.get("name")
    path = bench_dir / "references" / f"{name}.py"
    if name is None or not path.is_file():
        return Reference(Path(reference.__file__), reference.logits_in_blocks,
                         model.make_params, work.layers_of)
    mod = _load(path, "bench_reference_" + name.replace(".", "_")
                .replace("-", "_"))
    if not callable(getattr(mod, "logits_in_blocks", None)):
        raise ValueError(f"{path} defines no logits_in_blocks")
    return Reference(path, mod.logits_in_blocks,
                     getattr(mod, "make_params", model.make_params),
                     getattr(mod, "layers_of", work.layers_of))


def families(conf: Dict = None, bench_dir: Path = BENCH) -> Dict:
    """``families.json`` with the configuration's own ``"families"``
    merged into its ``kernels``.  A trace event counts for every family
    whose prefix it starts with, so two families whose prefixes overlap
    (one starting with the other) are refused."""
    fams = _json(bench_dir / "families.json")
    kernels = {k: list(v) for k, v in fams["kernels"].items()}
    for fam, prefixes in (conf or {}).get("families", {}).items():
        own = kernels.setdefault(fam, [])
        own += [p for p in prefixes if p not in own]
    claims = [(p, fam) for fam, ps in kernels.items() for p in ps]
    for p, fam in claims:
        for q, other in claims:
            if fam != other and p.startswith(q):
                raise ValueError(f"kernel prefix {p!r} of family {fam!r} "
                                 f"is claimed by family {other!r} too")
    return dict(fams, kernels=kernels)
