"""Every entry of BENCHMARK.json resolves to its files by name, and the
files agree with the entries and with the contract's rules."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys_and_paths():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert B["command"][1] == "bench/run.py"
    assert (ROOT / B["command"][1]).is_file()
    assert 1 <= B["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in (B["configs"], B["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert any(w["config"] == conf["name"] for w in B["workloads"])
    assert all(len(row) >= 9 for row in data["layers"])


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_config_resolves_its_reference_and_families(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    ref = spec.reference_of(data)
    assert callable(ref.logits_in_blocks) and ref.path.is_file()
    layers = ref.layers_of(data["layers"])
    assert [l.name for l in layers] == [row[0] for row in data["layers"]]
    prefixes = [p for ps in spec.families(data)["kernels"].values()
                for p in ps]
    assert len(prefixes) == len(set(prefixes))


@pytest.mark.parametrize("cell,metrics", [
    ("resnet50-x4-staged", {"images_per_s", "setup_s", "mfu",
                            "idle_share.sat", "collective_share",
                            "step_ms.sat", "conv_roofline", "warmup_s"}),
])
def test_cell_reports_exactly(cell, metrics):
    c = spec.load_cell(cell)
    assert c.chips == 4
    assert {m["name"] for m in c.end_to_end + c.per_layer} == metrics


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports_what_it_must(cell):
    c = spec.load_cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names, (cell, m["name"])
    assert c.mix["loop"] in ("closed", "open")
    assert c.workload["serve"]["entry"] in ("serve", "serve_sharded")
    assert set(c.workload["check"]["limits"]) >= {"logit_gap"}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    read = spec.metric_reader(metric["name"])
    assert callable(read)
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_per_layer_metrics_name_an_end_to_end_metric_and_a_layer():
    e2e = {m["name"] for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert m["layer"] and "\n" not in m["layer"]


def test_shares_are_percent():
    for m in METRICS:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_peaks_are_keyed_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
