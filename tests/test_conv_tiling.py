"""The dense conv kernel's tile rule at the benchmark's real widths.

The kernel sweeps each conv in tiles of ``bt`` images x ``r`` output rows
(``kernels/conv2d_int8/kernel.py::conv_tile``).  These tests hold the
rule to its contract over every conv of the benchmark's ResNet-50 and
VGG-16 at every dispatch batch the serving ladder uses, and hold the
compiler's plan for both networks where it was before the tiling: the
tile has a VMEM budget of its own, so it moves no placement or binding.
Nothing here runs a kernel.
"""
from __future__ import annotations

import json
import os
import re

import pytest

from repro import compiler
from repro.compiler.engines import select_engine
from repro.compiler.target import get_target
from repro.configs.cnn import CNNConfig, ConvLayerSpec
from repro.kernels.conv2d_int8 import kernel as K
from repro.kernels.conv2d_int8.ops import conv_tile_for
from repro.kernels.pallas_compat import SUBLANES, round_up

BENCH_CONFIGS = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                             "configs")
BATCHES = (1, 2, 4, 8, 16)


def _bench(name: str):
    """The benchmark configuration's network and target, as compiled."""
    with open(os.path.join(BENCH_CONFIGS, f"{name}.json")) as f:
        conf = json.load(f)
    cfg = CNNConfig(conf["network"],
                    tuple(ConvLayerSpec(*row) for row in conf["layers"]),
                    num_classes=conf["num_classes"])
    t = conf["target"]
    return cfg, get_target(t["preset"]).replace(**t.get("overrides", {}))


@pytest.fixture(scope="module", params=["resnet50", "vgg16"])
def bench_cp(request):
    cfg, target = _bench(request.param)
    return request.param, compiler.compile(cfg, target)


def _dense_convs(cp):
    return [s for s in cp.schedules
            if select_engine(s.spec).name == "conv2d_int8"]


@pytest.mark.parametrize("batch", BATCHES)
def test_tile_rule_divides_and_fits(bench_cp, batch):
    """``r`` divides H_out, ``bt`` divides the batch, images stack only
    once a tile covers the map, the tile keeps within its M cap and its
    working set within the VMEM budget (and so Mosaic's scoped limit),
    and the compiler reports the tile the kernel applies."""
    _, cp = bench_cp
    scheds = _dense_convs(cp)
    assert scheds
    for s in scheds:
        sp = s.spec
        t = conv_tile_for((batch, sp.in_h, sp.in_w, sp.c_in),
                          (sp.k_h, sp.k_w, sp.c_in, sp.c_out),
                          stride=sp.stride, stream=s.streamed,
                          n_buffers=s.n_buffers)
        assert sp.out_h % t.r == 0 and batch % t.bt == 0, (sp.name, t)
        assert t.bt == 1 or t.r == sp.out_h, (sp.name, t)
        assert t.w_pad == round_up(sp.out_w, SUBLANES)
        assert t.bt * t.r * t.w_pad <= K.TILE_M, (sp.name, t)
        assert t.vmem <= K.TILE_VMEM_BUDGET <= K.VMEM_LIMIT, (sp.name, t)
        g = select_engine(sp).grid(sp, s, batch)
        assert (g.bt, g.r) == (t.bt, t.r)
        assert g.steps == (batch // t.bt) * (sp.out_h // t.r)


@pytest.mark.parametrize("name,steps_per_image,streamed_mb_per_image", [
    ("resnet50", 150, 12), ("vgg16", 250, 25)])
def test_tiles_cut_steps_and_weight_reads(name, steps_per_image,
                                          streamed_mb_per_image):
    """At the saturation cells' microbatch of 16, the conv kernel takes
    at most ``steps_per_image`` grid steps per image over the network,
    and its streamed layers DMA at most ``streamed_mb_per_image`` MB of
    weights per image (one output row per step took 1,421 and 967 steps,
    and re-read 79 and 268 MB)."""
    cfg, target = _bench(name)
    cp = compiler.compile(cfg, target)
    grids = [select_engine(s.spec).grid(s.spec, s, 16)
             for s in _dense_convs(cp)]
    assert sum(g.steps for g in grids) / 16 <= steps_per_image
    assert sum(g.weight_bytes for g in grids) / 16 / 1e6 \
        <= streamed_mb_per_image


#: the compile() plan of the benchmark's networks before the tiled kernel:
#: streamed set, fused units and scan groups
PLANS = {
    "resnet50": dict(
        streamed=("s3b0c1", "s3b0c2", "s3b0ds", "s3b1c0", "s3b1c1",
                  "s3b2c1", "fc"),
        blocks=("s0b0", "s0b1", "s0b2", "s1b0", "s1b1", "s1b2", "s1b3",
                "s2b0", "s2b1", "s2b2", "s2b3", "s2b4", "s2b5", "s3b0",
                "s3b1", "s3b2", "stem"),
        scans={"scan:s0b1..s0b2": ("s0b1", "s0b2"),
               "scan:s1b1..s1b3": ("s1b1", "s1b2", "s1b3"),
               "scan:s2b2..s2b5": ("s2b2", "s2b3", "s2b4", "s2b5")},
        engines={"stem": "stem_pool_int8", "maxpool": "stem_pool_int8",
                 "gap": "global_avgpool_int8", "fc": "stream_matmul"}),
    "vgg16": dict(
        streamed=("conv8", "conv9", "conv10", "fc0", "fc1", "fc2"),
        blocks=(), scans={},
        engines={"fc0": "conv2d_int8", "fc1": "stream_matmul",
                 "fc2": "stream_matmul",
                 **{f"pool{i}": "maxpool_int8" for i in range(5)},
                 **{f"conv{i}": "conv2d_int8" for i in range(13)}}),
}


def test_plan_unchanged_and_described(bench_cp):
    """compile() keeps the benchmark networks' plan (streamed set, block,
    scan and stem bindings), and ``describe()`` prints each conv layer's
    tile and grid steps at the dispatch batch asked for."""
    name, cp = bench_cp
    want = PLANS[name]
    assert cp.streamed_names == want["streamed"]
    assert tuple(cp.block_table()) == want["blocks"]
    assert cp.scan_table() == want["scans"]
    table = cp.engine_table()
    for layer, engine in want["engines"].items():
        assert table[layer] == engine, layer
    res_members = [m for b in cp.block_assignments if b.block != "stem"
                   for m in b.members]
    assert all(table[m] == ("scanned_res_block_int8" if cp.scan_for(m)
                            else "res_block_int8") for m in res_members)
    assert len(table) == len(cp.cfg.layers)

    rows = {line.split()[0]: line for line in cp.describe(16).splitlines()}
    for s in _dense_convs(cp):
        g = select_engine(s.spec).grid(s.spec, s, 16)
        row = rows[s.spec.name]
        assert re.search(rf"\s{g.bt}x{g.r}\s+{g.steps}\s", row), row
        if s.streamed:
            assert row.split()[-1] == str(g.weight_bytes), row
