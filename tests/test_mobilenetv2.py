"""MobileNetV2's mechanisms in the program: inverted residuals with a
linear join, ReLU6, and the depthwise engine under its own name.

The full table is pinned (published depth and widths); a mini MobileNetV2
with every kind of block compiles to the block, scan and depthwise
engines and runs bit-identically to ``cnn_forward``; ResNet's blocks keep
their activations, joins and signatures."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler
from repro.configs.cnn import (CNN_CONFIGS, LINEAR, RELU, RELU6,
                               block_shape_signature, homogeneous_block_runs,
                               layer_activations, mini_mobilenetv2,
                               mini_resnet50, residual_blocks, stem_unit)
from repro.kernels.quant import requant_epilogue, residual_join
from repro.models.cnn import cnn_forward, init_cnn_params

MNV2 = CNN_CONFIGS["mobilenetv2"]
SKIPS = ["ir2", "ir4", "ir5", "ir7", "ir8", "ir9", "ir11", "ir12", "ir14",
         "ir15"]


def test_the_full_table_is_pinned():
    assert len(MNV2.layers) == 54
    assert MNV2.total_macs() == 300_774_272
    assert MNV2.total_weight_bits() // 8 == 3_469_760
    blocks = residual_blocks(MNV2)
    assert [b.name for b in blocks] == SKIPS
    for b in blocks:
        assert [m.kind for m in b.members] == ["pwconv", "dwconv", "pwconv"]
        assert b.acts == (RELU6, RELU6, LINEAR) and b.join == LINEAR
        assert b.ds is None and b.members[0].c_in == b.members[-1].c_out
    assert [[b.name for b in run] for run in homogeneous_block_runs(MNV2)] \
        == [["ir4", "ir5"], ["ir7", "ir8", "ir9"], ["ir11", "ir12"],
            ["ir14", "ir15"]]


def test_every_layer_has_its_published_activation():
    acts = layer_activations(MNV2)
    for l in MNV2.layers:
        if l.name.endswith("pj") or l.kind in ("gap", "fc"):
            assert acts[l.name] == LINEAR, l.name
        else:
            assert acts[l.name] == RELU6, l.name


@pytest.mark.parametrize("name", ["resnet18", "resnet50"])
def test_resnet_blocks_keep_their_activations_joins_and_runs(name):
    cfg = CNN_CONFIGS[name]
    for b in residual_blocks(cfg):
        want = (RELU,) * (len(b.convs) - 1) + (LINEAR,) * (
            1 + (b.ds is not None))
        assert b.acts == want and b.join == RELU
        assert block_shape_signature(b)[:4] == (
            len(b.convs), b.ds is not None, want, RELU)
    acts = layer_activations(cfg)
    in_block = {m.name for b in residual_blocks(cfg) for m in b.members}
    assert all(acts[l.name] == RELU for l in cfg.layers
               if l.name not in in_block and not l.is_pool and l.kind != "fc")
    assert acts["fc"] == LINEAR and stem_unit(cfg).act == RELU
    if name == "resnet50":
        assert [[b.name for b in r] for r in homogeneous_block_runs(cfg)] \
            == [["s0b1", "s0b2"], ["s1b1", "s1b2", "s1b3"],
                ["s2b1", "s2b2", "s2b3", "s2b4", "s2b5"], ["s3b1", "s3b2"]]


def test_other_networks_have_no_blocks_and_relu():
    for name in ("vgg16", "mobilenetv1"):
        cfg = CNN_CONFIGS[name]
        assert residual_blocks(cfg) == ()
        acts = layer_activations(cfg)
        assert {acts[l.name] for l in cfg.layers[:-1] if not l.is_pool} \
            == {RELU}


def test_requant_relu6_clips_the_float_before_rounding():
    acc = jnp.array([[-400, 10, 119, 121, 130, 5000]], jnp.int32)
    one = jnp.ones((6,), jnp.float32)
    zero = jnp.zeros((6,), jnp.float32)
    q6, y6 = requant_epilogue(acc, one, zero, act_scale=0.05, act=RELU6)
    # y = acc * 0.05: ReLU6 holds [0, 6.0], so 121 and up land on int8 120
    assert q6.tolist() == [[0, 10, 119, 120, 120, 120]]
    assert float(jnp.max(y6)) == 6.0
    qr, _ = requant_epilogue(acc, one, zero, act_scale=0.05, act=RELU)
    assert qr.tolist() == [[0, 10, 119, 121, 127, 127]]
    qn, _ = requant_epilogue(acc, one, zero, act_scale=0.05, act=LINEAR)
    assert qn.tolist() == [[-127, 10, 119, 121, 127, 127]]
    with pytest.raises(ValueError, match="activation"):
        requant_epilogue(acc, one, zero, act="gelu")


def test_the_linear_join_keeps_negative_sums():
    h = jnp.array([-100, -5, 60, 100], jnp.int8)
    x = jnp.array([-100, 3, 60, 100], jnp.int8)
    assert residual_join(h, x, LINEAR).tolist() == [-127, -2, 120, 127]
    assert residual_join(h, x, RELU).tolist() == [0, 0, 120, 127]


def test_the_mini_has_every_kind_of_block():
    cfg = mini_mobilenetv2()
    assert cfg.name.startswith("mobilenetv2")
    blocks = {b.name: b for b in residual_blocks(cfg)}
    assert sorted(blocks) == ["ir0", "ir2", "ir3"]
    assert [m.kind for m in blocks["ir0"].members] == ["dwconv", "pwconv"]
    assert blocks["ir0"].acts == (RELU6, LINEAR)
    names = [l.name for l in cfg.layers]
    assert "ir1dw" in names and "ir4pj" in names     # no skip: plain layers
    assert [[b.name for b in r] for r in homogeneous_block_runs(cfg)] \
        == [["ir2", "ir3"]]


def test_the_mini_compiles_to_block_scan_and_depthwise_engines():
    cp = compiler.compile(mini_mobilenetv2(), compiler.TPU_INTERPRET)
    table = cp.engine_table()
    assert "jnp_ref" not in table.values()
    assert cp.block_table()["ir0"] == ("ir0dw", "ir0pj")
    assert cp.scan_table() == {"scan:ir2..ir3": ("ir2", "ir3")}
    assert table["ir1dw"] == table["ir4dw"] == "dwconv_int8"
    assert table["ir2dw"] == "scanned_res_block_int8"
    rep = cp.eq2_report(batch=2).verify()
    assert rep.dispatch_counts() == {
        "conv2d_int8": 6, "dwconv_int8": 2, "res_block_int8": 2,
        "scanned_res_block_int8": 6, "global_avgpool_int8": 1,
        "stream_matmul": 1}


@pytest.mark.parametrize("scan", [True, False])
def test_the_compiled_mini_equals_cnn_forward(scan):
    cfg = mini_mobilenetv2()
    params = init_cnn_params(jax.random.PRNGKey(3), cfg)
    x = jax.random.randint(jax.random.PRNGKey(4), (2, 16, 16, 3), -127, 128,
                           jnp.int8)
    cp = compiler.compile(cfg, compiler.TPU_INTERPRET, scan=scan)
    got, rep = cp.run(params, x)
    rep.verify()
    assert rep.dispatch_counts() == cp.eq2_report(batch=2).dispatch_counts()
    want = jax.jit(lambda p, x: cnn_forward(p, cfg, x))(params, x)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_partition_keeps_inverted_residuals_whole():
    cp = compiler.compile(mini_mobilenetv2(), compiler.TPU_INTERPRET)
    names = [l.name for l in cp.cfg.layers]
    whole = [("ir0dw", "ir0pj"), ("ir2ex", "ir3pj")]
    for n in (2, 3, 4):
        for st in cp.partition(n).stages:
            start, stop = st.layer_range
            for a, b in whole:
                i, j = names.index(a), names.index(b)
                assert not (i < start <= j) and not (i < stop <= j)


def test_mini_resnet50_blocks_keep_the_relu_join():
    cfg = mini_resnet50()
    b = residual_blocks(cfg)[0]
    assert b.join == RELU and b.acts == (RELU, RELU, LINEAR, LINEAR)
