"""The benchmark's own work arithmetic against the program's tables, and
the plain reference against the program's reference forward pass."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import model, reference, work  # noqa: E402


def _table(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,macs", [("resnet50", 4_089_184_256),
                                       ("vgg16", 15_470_264_320)])
def test_macs_match_the_program_tables(name, macs):
    from repro.configs.cnn import CNN_CONFIGS
    layers = work.layers_of(_table(name)["layers"])
    assert work.macs_per_image(layers) == macs
    assert macs == CNN_CONFIGS[name].total_macs()
    assert sum(l.weight_bytes for l in layers) \
        == CNN_CONFIGS[name].total_weight_bits() // 8


def test_minimal_bytes_of_a_layer():
    stem = work.Layer("stem", "conv", 7, 7, 3, 64, 2, 224, 224)
    assert stem.out_hw == (112, 112)
    assert stem.act_bytes == 224 * 224 * 3 + 112 * 112 * 64
    assert stem.weight_bytes == 7 * 7 * 3 * 64
    fc0 = work.Layer("fc0", "fc", 7, 7, 512, 4096, 7, 7, 7)
    assert fc0.out_hw == (1, 1) and fc0.macs == 7 * 7 * 512 * 4096
    dw = work.Layer("dw0", "dwconv", 3, 3, 32, 32, 1, 112, 112)
    assert dw.macs == 9 * 32 * 112 * 112 and dw.weight_bytes == 9 * 32


def test_least_seconds_takes_the_larger_bound_per_layer():
    fc = work.Layer("fc1", "fc", 1, 1, 4096, 4096, 1, 1, 1)
    conv = work.Layer("c", "conv", 3, 3, 256, 256, 1, 56, 56)
    fam = {"fc1": "fc", "c": "conv"}
    t = work.least_seconds([fc, conv], images=16, dispatches=1,
                           peak_ops=393e12, peak_bytes=819e9,
                           family=lambda l: fam[l.name])
    assert t["fc"] == pytest.approx((4096 * 4096 + 16 * 8192) / 819e9)
    assert t["conv"] == pytest.approx(2 * conv.macs * 16 / 393e12)


def test_kernel_family_follows_the_binding():
    fc0 = work.Layer("fc0", "fc", 7, 7, 512, 4096, 7, 7, 7)
    pool = work.Layer("p", "maxpool", 2, 2, 64, 64, 2, 224, 224)
    assert work.kernel_family(fc0, "conv2d_int8", ["stream_matmul"]) == "conv"
    assert work.kernel_family(fc0, "stream_matmul", ["stream_matmul"]) == "fc"
    assert work.kernel_family(pool, "maxpool_int8", []) == "pool"


@pytest.mark.parametrize("builder", ["mini_resnet50", "mini_resnet18",
                                     "mini_mobilenet"])
def test_reference_equals_the_program_reference_bit_for_bit(builder):
    import repro.configs.cnn as cnn
    from repro.models.cnn import cnn_forward
    cfg = getattr(cnn, builder)()
    rows = [[l.name, l.kind, l.k_h, l.k_w, l.c_in, l.c_out, l.stride,
             l.in_h, l.in_w] for l in cfg.layers]
    params = model.make_params(model.seed_key(2 ** 31 + 3), rows, 0.05)
    x = model.image_pool(7, 3, rows[0][7:9] + [rows[0][4]])
    want = np.asarray(jax.jit(lambda p, x: cnn_forward(p, cfg, x))(params, x))
    got = reference.logits_in_blocks(params, rows, x, act_scale=0.05,
                                     block=2)
    assert np.array_equal(got, want)
    assert np.std(got) > 0.5                 # not saturated, not vanished


@pytest.mark.parametrize("cell", ["resnet50-sat", "resnet50-online",
                                  "resnet50-x4-staged"])
def test_the_int4_control_fails_the_cells_comparison(cell):
    """``bench/control.py`` at mini size: the reference with int4 weights
    in the program's place is not correct under the cell's own limits."""
    import importlib.util
    from repro.configs.cnn import mini_resnet50
    from benchlib import spec
    s = importlib.util.spec_from_file_location("bench_control",
                                               BENCH / "control.py")
    control = importlib.util.module_from_spec(s)
    s.loader.exec_module(control)
    cfg = mini_resnet50()
    rows = [[l.name, l.kind, l.k_h, l.k_w, l.c_in, l.c_out, l.stride,
             l.in_h, l.in_w] for l in cfg.layers]
    c = spec.load_cell(cell)
    c.config = dict(c.config, layers=rows, image=[32, 32, 3])
    c.workload = dict(c.workload, pool_images=32,
                      check=dict(c.workload["check"], requests=3, block=4))
    for seed in (1, 2 ** 31 + 1):
        out = control.control(c, seed)
        assert out["correct"] is False
        assert out["logit_gap"]["value"] > 0.05 > out["logit_gap"]["limit"]


def test_params_are_the_same_for_the_same_seed_and_int8():
    rows = _table("resnet50")["layers"][:3]
    a = model.make_params(model.seed_key(2 ** 32 + 1), rows, 0.05)
    b = model.make_params(model.seed_key(2 ** 32 + 1), rows, 0.05)
    c = model.make_params(model.seed_key(2 ** 32 + 2), rows, 0.05)
    assert a["stem"]["w"].dtype == np.int8
    assert a["stem"]["w"].shape == (7, 7, 3, 64)
    assert np.array_equal(a["stem"]["w"], b["stem"]["w"])
    assert not np.array_equal(a["stem"]["w"], c["stem"]["w"])


@pytest.mark.parametrize("cell", sorted(
    p.stem for p in (BENCH / "workloads").glob("*.json")))
def test_logit_limit_admits_an_epilogue_rounded_otherwise(cell):
    """The served logits are the int8 accumulators dequantized in float32;
    the same epilogue associated otherwise (folded scales, a fused
    multiply-add) moves a logit by an ulp or two.  That stays correct
    under each workload's limit, while one int8 step of the last layer's
    input does not."""
    from benchlib import check
    limits = json.loads((BENCH / "workloads" / f"{cell}.json")
                        .read_text())["check"]["limits"]
    rng = np.random.default_rng(5)
    want = (rng.standard_normal((8, 1000)) * 4).astype(np.float32)
    ulps = np.nextafter(np.nextafter(want, np.inf), np.inf)
    assert check.compare(ulps, want, 0, limits)[0]
    step = want.copy()
    step[:, 7] += 0.05 * 0.01                   # act_scale x a small w_scale
    assert not check.compare(step, want, 0, limits)[0]
