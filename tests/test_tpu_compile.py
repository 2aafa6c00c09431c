"""Main-path kernels compiled at real widths for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses: unaligned DMA slices,
strided int8 slices, int8 VPU arithmetic, block shapes off the (8, 128)
tiling, more VMEM than Mosaic's scoped limit.  These tests lower each
engine's kernel at the widths the paper's networks use (ResNet-50 /
VGG-16 / MobileNetV2 at 224x224, batch 8; VGG-16's convs at 1 and 16) and
compile it with the TPU compiler for a chip that is described, not
attached.  Nothing runs; a pass means the chip's compiler accepts the
kernel and emits a Mosaic custom call for it.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler library.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compiler.engines import StreamMatmulFCEngine
from repro.configs.cnn import ConvLayerSpec
from repro.kernels.conv2d_int8.ops import conv2d_int8, dwconv_int8
from repro.kernels.pool_int8.ops import global_avgpool_int8, maxpool_int8
from repro.kernels.stream_matmul.ops import stream_matmul

B = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, one_chip, *shapes):
    """Lower ``fn`` on int8 arguments of ``shapes`` placed on the
    described chip, compile it, and return the compiled HLO text."""
    args = [jax.ShapeDtypeStruct(s, jnp.int8, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# (H=W, C_in, C_out, k, stride, streamed): where each sits in the networks
CONV_CASES = {
    "stem_7x7s2_c3": (224, 3, 64, 7, 2, False),        # ResNet stem
    "conv3x3s1_pinned": (56, 64, 64, 3, 1, False),     # ResNet-50 s0 c1
    "conv3x3s2_pinned": (56, 128, 128, 3, 2, False),   # ResNet-50 s1b0 c1
    "conv3x3s1_streamed": (14, 256, 256, 3, 1, True),  # ResNet-50 s2 c1
    "conv3x3s2_streamed": (14, 512, 512, 3, 2, True),  # ResNet-50 s3b0 c1
    "conv3x3s1_7x7_c512": (7, 512, 512, 3, 1, True),   # ResNet-50 s3 c1
    "pw1x1_c64": (56, 64, 256, 1, 1, False),           # ResNet-50 s0 c2
    "pw1x1s2_ds": (56, 256, 512, 1, 2, False),         # ResNet-50 s1b0 ds
    "vgg_conv1_224_c64": (224, 64, 64, 3, 1, False),   # VGG-16 conv1
    "vgg_conv8_streamed": (28, 512, 512, 3, 1, True),  # VGG-16 conv8
    "vgg_fc0_7x7s7_streamed": (7, 512, 4096, 7, 7, True),  # VGG-16 fc0
}

#: VGG-16's shapes also at the saturation cells' microbatch and at one
#: image, the largest and the smallest tiles the rule gives them
VGG_CASES = sorted(c for c in CONV_CASES if c.startswith("vgg_"))


def _compile_conv(one_chip, case, batch):
    hw, c_in, c_out, k, stride, stream = CONV_CASES[case]
    _compile_for_chip(
        lambda x, w: conv2d_int8(x, w, stride=stride, stream=stream,
                                 interpret=False),
        one_chip, (batch, hw, hw, c_in), (k, k, c_in, c_out))


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv2d_int8_compiles(one_chip, case):
    _compile_conv(one_chip, case, B)


@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("case", VGG_CASES)
def test_conv2d_int8_compiles_at_batch(one_chip, case, batch):
    _compile_conv(one_chip, case, batch)


#: every depthwise shape of MobileNetV2 (ir0 ... ir16) as (stride, H=W, C)
DW_CASES = [(1, 112, 32), (2, 112, 96), (1, 56, 144), (2, 56, 144),
            (1, 28, 192), (2, 28, 192), (1, 14, 384), (1, 14, 576),
            (2, 14, 576), (1, 7, 960)]


@pytest.mark.parametrize("stride,hw,c", DW_CASES)
@pytest.mark.parametrize("stream", [False, True])
def test_dwconv_int8_compiles(one_chip, stride, hw, c, stream):
    """MobileNetV2's depthwise 3x3 at each of its shapes, from 112x112x32
    (ir0) and 112x112x96 at stride 2 (ir1) to 7x7x960 (ir16), under its
    own entry point's name."""
    text = _compile_for_chip(
        lambda x, w: dwconv_int8(x, w, stride=stride, stream=stream,
                                 interpret=False),
        one_chip, (B, hw, hw, c), (3, 3, 1, c))
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(", text)
    assert calls and all(c.startswith("dwconv_int8") for c in calls), calls


def test_maxpool_int8_compiles(one_chip):
    """The ResNet stem pool: 3x3/2 on 112x112x64."""
    _compile_for_chip(
        lambda x: maxpool_int8(x, k=3, stride=2, interpret=False),
        one_chip, (B, 112, 112, 64))


def test_global_avgpool_int8_compiles(one_chip):
    """ResNet-50's GAP over the 7x7x2048 map."""
    _compile_for_chip(
        lambda x: global_avgpool_int8(x, act_scale=0.05, interpret=False),
        one_chip, (B, 7, 7, 2048))


@pytest.mark.parametrize("mode", ["pinned", "fifo"])
def test_stream_matmul_fc1000_compiles(one_chip, mode):
    """The 1000-class head at the engine's own block sizes (N = 1000 is
    no multiple of 128, so it runs as one full-width N block)."""
    spec = ConvLayerSpec("fc", "fc", 1, 1, 2048, 1000, 1, 1, 1)
    bm, bk, bn = StreamMatmulFCEngine().blocks(B, spec)
    assert bn == 1000
    _compile_for_chip(
        lambda x, w: stream_matmul(x, w, mode=mode, bm=bm, bk=bk, bn=bn,
                                   interpret=False),
        one_chip, (B, 2048), (2048, 1000))
