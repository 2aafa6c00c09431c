"""MobileNetV2's configuration, its own reference and its depthwise
roofline.  The rows' trailing columns (an activation and a join) are the
reference's only wiring; the program derives the same semantics from the
network's name and its layer names.  The two descriptions are written
apart, and a mini MobileNetV2 compiled and served on the Pallas
interpreter agrees with the reference bit for bit."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import check, model, spec, work  # noqa: E402
from benchlib.record import Run  # noqa: E402

from test_bench_run import _run  # noqa: E402

CONF = json.loads((BENCH / "configs" / "mobilenetv2.json").read_text())
REF = spec.reference_of(CONF)
LIMITS = json.loads((BENCH / "workloads" / "mobilenetv2-sat.json")
                    .read_text())["check"]["limits"]


def _rows(cfg):
    """``cfg``'s rows with the two trailing columns, by the paper's rule
    (Fig. 4(d)): ReLU6 after every conv but the linear projections
    (``pj``), the pool and the logits; a skip from the block's input to
    its projection where the depthwise stride is 1 and the widths agree,
    with no activation after the add."""
    rows, first = [], {}
    for l in cfg.layers:
        block, part = l.name[:-2], l.name[-2:]
        first.setdefault(block, l.name)
        linear = part == "pj" or l.kind in ("gap", "fc")
        row = [l.name, l.kind, l.k_h, l.k_w, l.c_in, l.c_out, l.stride,
               l.in_h, l.in_w, "none" if linear else "relu6", None]
        if part == "pj":
            dw = rows[-1]
            lead = next(r for r in rows if r[0] == first[block])
            if dw[6] == 1 and lead[4] == l.c_out:
                row[10] = {"skip_from": first[block], "act": "none"}
        rows.append(row)
    return rows


def _mini():
    from repro.configs.cnn import mini_mobilenetv2
    cfg = mini_mobilenetv2()
    return cfg, _rows(cfg)


def test_the_configuration_is_the_program_table_and_the_paper():
    from repro.configs.cnn import CNN_CONFIGS
    cfg = CNN_CONFIGS["mobilenetv2"]
    assert [row[:9] for row in CONF["layers"]] == [
        list(dataclasses.astuple(l)) for l in cfg.layers]
    assert CONF["layers"] == _rows(cfg)
    assert CONF["layers_format"][9:] == ["act", "join"]
    assert sum(1 for row in CONF["layers"] if row[10]) == 10


def test_the_work_counts_are_pinned():
    layers = REF.layers_of(CONF["layers"])
    assert len(layers) == 54
    assert work.macs_per_image(layers) == 300_774_272
    assert sum(l.weight_bytes for l in layers) == 3_469_760
    dw = [l for l in layers if getattr(l, "family", None) == "dwconv"]
    assert [l.name for l in dw] == [f"ir{i}dw" for i in range(17)]
    assert sum(l.macs for l in dw) == 20_716_416
    # int8 activations across layer boundaries per image, 45 % depthwise
    assert sum(l.act_bytes for l in layers) == 13_510_312
    assert sum(l.act_bytes for l in dw) == 6_043_072


def test_depthwise_work_routes_to_its_family():
    layers = REF.layers_of(CONF["layers"])
    engines = {l.name: ("stream_matmul" if l.name == "fc"
                        else "dwconv_int8" if l.kind == "dwconv"
                        else "conv2d_int8") for l in layers}
    t = work.least_seconds(
        layers, images=3200, dispatches=100, peak_ops=393e12,
        peak_bytes=819e9, family=lambda l: work.kernel_family(
            l, engines[l.name], ["stream_matmul"]))
    assert set(t) == {"conv", "dwconv", "pool", "fc"}
    dw = [l for l in layers if l.kind == "dwconv"]
    assert t["dwconv"] == pytest.approx(sum(
        (l.act_bytes * 3200 + l.weight_bytes * 100) / 819e9 for l in dw))


def test_the_conv_and_dwconv_families_do_not_overlap():
    fams = spec.families(CONF)
    assert fams["kernels"]["conv"] == ["conv2d_int8"]
    assert fams["kernels"]["dwconv"] == ["dwconv_int8"]
    assert not any(p.startswith(q) for p in fams["kernels"]["dwconv"]
                   for q in fams["kernels"]["conv"])
    assert not any(q.startswith(p) for p in fams["kernels"]["dwconv"]
                   for q in fams["kernels"]["conv"])


def test_dwconv_roofline_reads_the_depthwise_time():
    layers = REF.layers_of(CONF["layers"])
    engines = {l.name: "conv2d_int8" for l in layers}
    trace = {"window_s": 1.0, "busy_s": 1.0, "dispatches": 4,
             "family_s": {"conv": 0.3, "dwconv": 0.5}}
    sent = [dataclasses.make_dataclass("S", ["n", "t_done"])(32, 0.5)
            for _ in range(4)]
    run = Run(seconds=1.0, t0=0.0, t_end=1.0, chips=1, setup_s=1.0,
              warmup_s=1.0, sent=sent, before={}, after={},
              peaks=spec.peaks("TPU v5 lite"), layers=layers,
              engines=engines, fc_engines=["stream_matmul"], trace=trace)
    read = spec.metric_reader("dwconv_roofline")
    least = run.least_seconds(4)["dwconv"]
    assert read(run) == pytest.approx(100 * least / 0.5)
    assert 0 < read(run) < 100
    # a program whose depthwise events carry another name: nothing to read
    run.trace = dict(trace, family_s={"conv": 0.8})
    assert read(run) is None


@pytest.mark.parametrize("path", ["run", "serve"])
def test_the_compiled_mini_is_bit_identical_to_the_reference(path):
    from repro import compiler
    cfg, rows = _mini()
    params = REF.make_params(model.seed_key(2 ** 31 + 11), rows, 0.05)
    x = model.image_pool(2 ** 33 + 5, 6, [16, 16, 3])
    cp = compiler.compile(cfg, compiler.TPU_INTERPRET)
    assert "jnp_ref" not in cp.engine_table().values()
    if path == "run":
        got, rep = cp.run(params, x)
        rep.verify()
    else:
        with cp.serve(params, microbatch=4, credits=2) as eng:
            got = np.concatenate([eng.submit(x[:2]).result(timeout=600),
                                  eng.submit(x[2:]).result(timeout=600)])
    want = REF.logits_in_blocks(params, rows, x, act_scale=0.05, block=4)
    assert np.array_equal(np.asarray(got), want)
    assert np.std(want) > 0.5                 # not saturated, not vanished


def _mutated(rows, how):
    out = [list(r) for r in rows]
    for r in out:
        if how == "relu-for-relu6" and r[9] == "relu6":
            r[9] = "relu"
        if how == "relu-after-join" and r[10]:
            r[10] = dict(r[10], act="relu")
    return out


@pytest.mark.parametrize("how", ["relu-for-relu6", "relu-after-join"])
def test_the_check_catches_a_wrong_activation(how):
    """At the benchmark's weights, ReLU in place of ReLU6, or a ReLU after
    the linear join, moves the logits far past the cell's limit."""
    _, rows = _mini()
    params = REF.make_params(model.seed_key(2 ** 31 + 11), rows, 0.05)
    x = model.image_pool(2 ** 33 + 5, 8, [16, 16, 3])
    want = REF.logits_in_blocks(params, rows, x, act_scale=0.05, block=8)
    got = REF.logits_in_blocks(params, _mutated(rows, how), x,
                               act_scale=0.05, block=8)
    ok, checks = check.compare(got, want, 0, LIMITS)
    assert not ok
    assert checks["logit_gap"]["value"] > 1e3 * LIMITS["logit_gap"]


def _relu6_share(rows, params, x):
    """The share of the expansion and depthwise outputs that ReLU6 holds
    at int8 120 (6.0 at act_scale 0.05), walking the reference's rows."""
    import jax.numpy as jnp
    mod = spec._load(REF.path, "bench_reference_mobilenetv2_walk")
    sources = {r[10]["skip_from"] for r in rows if r[10]}
    inputs, at_six, count = {}, 0, 0
    for row in rows:
        if row[0] in sources:
            inputs[row[0]] = x
        x, _ = mod._layer(params.get(row[0]), row, x, 0.05, 8)
        if row[9] == "relu6" and row[1] in ("pwconv", "dwconv"):
            at_six += int(jnp.sum(x == 120))
            count += x.size
        if row[10]:
            x = jnp.clip(x.astype(jnp.int32) + inputs[row[10]["skip_from"]],
                         -127, 127).astype(jnp.int8)
    return at_six / count


def test_relu6_clips_at_the_benchmarks_weights():
    """The shared weights make ReLU6 bite: at least 1 % of the expansion
    and depthwise outputs reach int8 120."""
    _, rows = _mini()
    params = REF.make_params(model.seed_key(2 ** 31 + 11), rows, 0.05)
    x = model.image_pool(2 ** 33 + 5, 8, [16, 16, 3])
    assert _relu6_share(rows, params, x) >= 0.01


def test_the_mini_cell_runs_correct_through_the_harness():
    """``run_cell`` finds the configuration's own reference by its name
    and the served answers agree with it."""
    cfg, rows = _mini()
    conf = dict(CONF, network=cfg.name, layers=rows, num_classes=10,
                image=[16, 16, 3],
                target={"preset": "tpu-interpret", "overrides": {}})
    wl = {"config": "mobilenetv2", "traffic": "mini", "chips": 1,
          "serve": {"entry": "serve", "microbatch": 4, "credits": 2},
          "pool_images": 32,
          "check": {"requests": 6, "block": 4, "limits": LIMITS}}
    mix = {"loop": "closed", "clients": 2, "images": {"min": 1, "max": 4}}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    e2e = [m for m in bench["end_to_end"] if m["name"] in
           ("images_per_s", "setup_s")]
    r = _run(spec.Cell("mini", wl, conf, mix, e2e, []))
    assert r["correct"] is True
    assert r["checks"]["logit_gap"]["value"] == 0.0
