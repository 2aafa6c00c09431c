"""Pooling topology nodes as Pallas TPU kernels.

H2PIPE emits a hardware engine for every CNN graph node — pooling
included: a maxpool engine is a line buffer plus comparator trees, a
global-average-pool engine is a per-channel accumulator bank.  The TPU
mapping follows the conv engine (``kernels/conv2d_int8``):

``_maxpool_kernel``   grid (B, H_out); a VMEM line buffer holds the k_h
                      input rows under the window (DMA'd per output row,
                      in the conv engine's phase-split line layout), and
                      the k_h x k_w taps reduce with an int32
                      ``jnp.maximum`` on the VPU — comparators, no MACs,
                      no weights, no Eq. 2 traffic.
``_gap_kernel``       grid (B,); the (small, end-of-net) spatial map sits
                      in VMEM and channels accumulate in int32 (exact —
                      the sums fit f32's integer range).  The ops
                      wrapper turns the sums into the requantized mean
                      with the reference's own float ops in XLA, so the
                      1x1 int8 map is bit-identical to the float32
                      reference on any backend.

Inputs are pre-padded by the ops wrapper (maxpool pads with int8 -128,
the identity of max — the float reference pads with +inf under min; both
can never win), so kernels have no boundary conditionals.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv2d_int8.kernel import (fill_line_buffer,
                                              line_buffer_taps)


def _maxpool_kernel(x_hbm_ref, o_ref, rows_buf, sem, *,
                    k_h: int, k_w: int, stride: int, w_out: int):
    fill_line_buffer(x_hbm_ref, rows_buf, sem, k_h=k_h, stride=stride)
    # compare in int32: Mosaic has no int8 max on the v5e VPU
    acc = jnp.full((w_out, o_ref.shape[-1]), -128, jnp.int32)
    for _, _, cols in line_buffer_taps(rows_buf, k_h=k_h, k_w=k_w,
                                       stride=stride, w_out=w_out):
        acc = jnp.maximum(acc, cols.astype(jnp.int32))
    o_ref[0, 0] = acc.astype(jnp.int8)


def maxpool_int8_kernel(x_lines, *, k_h: int, k_w: int, stride: int,
                        w_out: int, interpret: bool = False):
    """x_lines: [B, H_pad, stride, W_phase, C] int8 in the conv engine's
    line layout (``conv2d_int8.ops.to_line_layout``, padded with -128);
    ``w_out`` is the true output width.  Returns [B, H_out, w_out, C]
    int8."""
    B, H_pad, phases, W_phase, C = x_lines.shape
    assert phases == stride, (x_lines.shape, stride)
    H_out = (H_pad - k_h) // stride + 1
    return pl.pallas_call(
        functools.partial(_maxpool_kernel, k_h=k_h, k_w=k_w, stride=stride,
                          w_out=w_out),
        grid=(B, H_out),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],   # activations in HBM
        out_specs=pl.BlockSpec((1, 1, w_out, C), lambda b, r: (b, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H_out, w_out, C), jnp.int8),
        scratch_shapes=[
            pltpu.VMEM((k_h, stride, W_phase, C), jnp.int8),  # line buffer
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x_lines)


def _gap_kernel(x_ref, o_ref):
    o_ref[0, 0, 0] = jnp.sum(x_ref[0].astype(jnp.int32), axis=(0, 1))


def global_avgpool_sum_kernel(x, *, interpret: bool = False):
    """x: [B, H, W, C] int8 -> [B, 1, 1, C] int32 exact channel sums."""
    B, H, W, C = x.shape
    return pl.pallas_call(
        _gap_kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, 1, C), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, 1, C), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(x)
