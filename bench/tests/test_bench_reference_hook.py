"""A configuration brings its own layer semantics as files: a reference
module (``bench/references/<config>.py``), its work counts (``layers_of``
and a layer's ``family``) and its kernel families (``"families"``).  The
check of a run calls the configuration's own functions, and the two
configurations that bring none read exactly what they read before.

The test modules live under ``bench/tests/data/references/``, each
named as the configuration that takes it."""
from __future__ import annotations

import dataclasses
import functools
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

from benchlib import model, reference, spec, work  # noqa: E402
from benchlib.record import Run  # noqa: E402

from test_bench_run import _mini_cell, _run, bench_run  # noqa: E402


def _conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _named(monkeypatch, name):
    """The mini ResNet-50 cell under the configuration name ``name``, its
    module looked up under the test data."""
    monkeypatch.setattr(spec, "reference_of", functools.partial(
        spec.reference_of, bench_dir=DATA))
    cell = _mini_cell()
    return dataclasses.replace(cell, config=dict(cell.config, name=name))


@pytest.mark.parametrize("conf", [{"layers": []}, {"name": "nowhere"},
                                  {"name": "resnet50"}])
def test_no_module_of_its_own_gives_the_shared_reference(conf):
    ref = spec.reference_of(conf, bench_dir=DATA)
    assert ref.logits_in_blocks is reference.logits_in_blocks
    assert ref.make_params is model.make_params
    assert ref.layers_of is work.layers_of


def test_a_configurations_module_is_loaded_with_the_defaults_it_leaves_out():
    ref = spec.reference_of({"name": "marked"}, bench_dir=DATA)
    assert ref.path == DATA / "references" / "marked.py"
    assert ref.make_params is not model.make_params
    assert ref.layers_of is work.layers_of
    shared = spec.reference_of({"name": "shared"}, bench_dir=DATA)
    assert shared.logits_in_blocks is reference.logits_in_blocks
    assert shared.make_params is model.make_params


def test_a_module_without_logits_in_blocks_is_refused():
    with pytest.raises(ValueError, match="references/incomplete.py"):
        spec.reference_of({"name": "incomplete"}, bench_dir=DATA)


def test_a_module_that_re_exports_the_shared_one_is_correct(
        monkeypatch):
    r = _run(_named(monkeypatch, "shared"))
    assert r["correct"] is True
    assert r["checks"]["logit_gap"]["value"] == 0.0


def test_the_check_calls_the_configurations_own_reference(monkeypatch):
    r = _run(_named(monkeypatch, "bumped"))
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


def test_the_configurations_weights_reach_the_program_and_the_reference(
        monkeypatch):
    from repro.compiler.pipeline import CompiledPipeline
    served = []
    orig = CompiledPipeline.serve

    def serve(self, params, **kw):
        served.append(float(params["mark"]))
        return orig(self, params, **kw)

    monkeypatch.setattr(CompiledPipeline, "serve", serve)
    r = _run(_named(monkeypatch, "marked"))
    assert served == [12345.0]
    assert r["correct"] is True       # marked.py raises without its mark
    assert r["checks"]["logit_gap"]["value"] == 0.0


def test_the_control_takes_the_configurations_own_reference(monkeypatch):
    import importlib.util
    s = importlib.util.spec_from_file_location("bench_control",
                                               BENCH / "control.py")
    control = importlib.util.module_from_spec(s)
    s.loader.exec_module(control)
    c = _named(monkeypatch, "marked")
    c.workload = dict(c.workload, check=dict(c.workload["check"],
                                             requests=3))
    out = control.control(c, 2 ** 31 + 1)     # marked.py raises without
    assert out["correct"] is False            # its own make_params
    assert out["logit_gap"]["value"] > 0.05


def test_rows_may_carry_trailing_columns():
    row = ["s0b0c2", "pwconv", 1, 1, 64, 256, 1, 56, 56]
    (plain,) = work.layers_of([row])
    (longer,) = work.layers_of([row + ["linear", {"join": "add"}]])
    assert longer == plain
    cfg = bench_run.program_config({"network": "n", "num_classes": 10,
                                    "layers": [row + ["linear"]]})
    assert [dataclasses.astuple(l) for l in cfg.layers] == [tuple(row)]


@dataclasses.dataclass(frozen=True)
class _SeLayer(work.Layer):
    family: str = "se"


def test_a_layers_family_routes_its_least_time():
    conv = work.Layer("c", "conv", 3, 3, 64, 64, 1, 56, 56)
    gate = _SeLayer("g", "pwconv", 1, 1, 64, 64, 1, 1, 1)
    run = Run(seconds=1.0, t0=0.0, t_end=1.0, chips=1, setup_s=1.0,
              warmup_s=1.0, sent=[], before={}, after={},
              peaks=spec.peaks("TPU v5 lite"), layers=(conv, gate),
              engines={"c": "conv2d_int8", "g": "conv2d_int8"},
              fc_engines=["stream_matmul"])
    t = run.least_seconds(dispatches=1)
    assert set(t) == {"conv", "se"}
    assert work.kernel_family(gate, "conv2d_int8", []) == "se"
    assert work.kernel_family(conv, "conv2d_int8", []) == "conv"


def test_a_configuration_adds_a_kernel_family():
    fams = spec.families({"families": {"dwconv": ["dwconv2d_int8"]}})
    assert fams["kernels"]["dwconv"] == ["dwconv2d_int8"]
    assert fams["kernels"]["conv"] == ["conv2d_int8"]
    assert spec.families()["kernels"].keys() == {"conv", "fc", "pool"}


@pytest.mark.parametrize("added", [
    {"dwconv": ["conv2d_int8"]},            # the same prefix
    {"dwconv": ["conv2d_int8_dw"]},         # an event both would count
    {"conv": ["se_gate"], "se": ["se_gate"]},
])
def test_a_prefix_claimed_by_two_families_is_refused(added):
    with pytest.raises(ValueError, match="claimed"):
        spec.families({"families": added})


# What the two configurations that name no module read at the parent
# commit: their reading may not move with the hooks above.

@pytest.mark.parametrize("name,macs", [("resnet50", 4_089_184_256),
                                       ("vgg16", 15_470_264_320)])
def test_the_shared_work_counts_are_pinned(name, macs):
    conf = _conf(name)
    ref = spec.reference_of(conf)
    assert ref.layers_of is work.layers_of
    assert work.macs_per_image(ref.layers_of(conf["layers"])) == macs


_LEAST = {
    "resnet50": {"conv": 0.23295321079715542, "pool": 0.009452307692307692,
                 "fc": 0.0012763565323565323},
    "vgg16": {"conv": 0.6353296862680796, "pool": 0.0654003418803419,
              "fc": 0.012856683760683761},
}


@pytest.mark.parametrize("name", sorted(_LEAST))
def test_the_shared_least_seconds_are_pinned(name):
    conf = _conf(name)
    fams = spec.families(conf)
    layers = spec.reference_of(conf).layers_of(conf["layers"])
    engine = {l.name: ("stream_matmul" if l.kind == "fc" and l.k_h == 1
                       else "conv2d_int8") for l in layers}
    t = work.least_seconds(
        layers, images=7000, dispatches=500, peak_ops=393e12,
        peak_bytes=819e9, family=lambda l: work.kernel_family(
            l, engine[l.name], fams["fc_engines"]))
    assert t == _LEAST[name]


@pytest.mark.parametrize("name", ["resnet50", "vgg16"])
def test_the_shared_families_are_pinned(name):
    assert spec.families(_conf(name)) == spec.families()
    assert spec.families()["kernels"] == {
        "conv": ["conv2d_int8"], "fc": ["stream_matmul"],
        "pool": ["maxpool_int8", "global_avgpool_int8"]}
    assert spec.families()["fc_engines"] == ["stream_matmul"]

