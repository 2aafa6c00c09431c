"""Jit'd wrapper: SAME padding + line layout + requantization around the
Pallas conv.

``stream=True`` selects the HBM-streamed weight path (W re-read once per
tile of output rows and images through a double-buffered VMEM ring); the
placement plan (core/schedule.py) flips that switch per layer, the way
the H2PIPE compiler instantiates either an on-chip weight buffer or an
HBM FIFO chain per layer engine.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.conv2d_int8.kernel import (ConvTile, conv2d_int8_kernel,
                                              conv_tile, dwconv_int8_kernel)
from repro.kernels.pallas_compat import LANES, SUBLANES, round_up
from repro.kernels.quant import requant_epilogue


def same_padded_width(n: int, k: int, stride: int) -> int:
    """Padded extent of one spatial dim under this module's SAME padding.
    The single source of truth for the kernel's line-buffer geometry —
    ``to_line_layout`` below, the pooling wrapper and the compile-time
    VMEM accounting (``repro.compiler.engines``, via
    :func:`line_buffer_geometry`) all derive from it, so they cannot
    desynchronize."""
    out = -(-n // stride)
    return n + max((out - 1) * stride + k - n, 0)


def line_buffer_geometry(n_w: int, c: int, k_w: int,
                         stride: int) -> Tuple[int, int, int]:
    """``(phases, phase_width, channels)`` of one line-buffer row for an
    input of width ``n_w`` and ``c`` channels: ``stride`` column phases,
    each ``ceil(W_pad / stride)`` wide — and wide enough that every tap's
    ``w_out`` columns, rounded up to SUBLANES as the tiled conv computes
    them, lie inside it — rounded up to SUBLANES; channels rounded up to
    LANES."""
    w_pad = same_padded_width(n_w, k_w, stride)
    w_out = -(-n_w // stride)
    phase = max(-(-w_pad // stride),
                (k_w - 1) // stride + round_up(w_out, SUBLANES))
    return stride, round_up(phase, SUBLANES), round_up(c, LANES)


def to_line_layout(x, k_h: int, k_w: int, stride: int, pad_value: int = 0):
    """SAME-pad ``x`` [B, H, W, C] and lay it out as the kernels' line
    buffer reads it: [B, H_pad, stride, W_phase, C_pad], where phase ``p``
    holds padded columns p, p + stride, p + 2*stride, ...  A stride-s tap
    at column offset j is then the contiguous slice ``[j // s :]`` of
    phase ``j % s``.  ``H_pad`` is a whole number of ``stride``-row
    groups, ``stride * (H_out + (k_h - 1) // stride)``, so the dense conv
    can view rows as row phases too.  Tiling padding (extra rows, columns
    and channels) is filled with ``pad_value`` and never read into a real
    output."""
    B, H, W, C = x.shape
    top = (same_padded_width(H, k_h, stride) - H) // 2
    rows = stride * (-(-H // stride) + (k_h - 1) // stride)
    pad_w = same_padded_width(W, k_w, stride) - W
    phases, w_phase, c_pad = line_buffer_geometry(W, C, k_w, stride)
    xp = jnp.pad(x, ((0, 0), (top, rows - H - top),
                     (pad_w // 2, phases * w_phase - W - pad_w // 2),
                     (0, c_pad - C)),
                 constant_values=jnp.asarray(pad_value, x.dtype))
    xp = xp.reshape(B, rows, w_phase, phases, c_pad)
    return jnp.transpose(xp, (0, 1, 3, 2, 4))


def conv_tile_for(x_shape, w_shape, *, stride: int, stream: bool,
                  n_buffers: int) -> ConvTile:
    """The ``conv_tile`` of ``conv2d_int8`` on an input of ``x_shape``
    [B, H, W, C] with weights of ``w_shape`` [k_h, k_w, C, C_out]: the
    line layout's geometry fed to the kernel's tile rule.  ``conv2d_int8``
    hands the kernel this tile, so what the compiler reports is what the
    kernel does."""
    B, H, W, C = x_shape
    k_h, k_w, _, c_out = w_shape
    _, w_phase, c_pad = line_buffer_geometry(W, C, k_w, stride)
    return conv_tile(batch=B, h_out=-(-H // stride), w_out=-(-W // stride),
                     w_phase=w_phase, c=c_pad, c_out=c_out, k_h=k_h,
                     k_w=k_w, stride=stride, stream=stream,
                     n_buffers=n_buffers)


@functools.partial(jax.jit, static_argnames=("stride", "stream", "n_buffers",
                                             "interpret"))
def conv2d_int8(x, w, *, stride: int = 1, stream: bool = False,
                n_buffers: int = 2, interpret: bool = False):
    """SAME conv, int8 in / int32 out, via the line-buffer Pallas kernel.

    Input channels are zero-padded to the lane tiling (with matching zero
    weight rows), so the padding contributes nothing to the sums.
    """
    k_h, k_w = w.shape[:2]
    xl = to_line_layout(x, k_h, k_w, stride)
    c_pad = xl.shape[-1] - x.shape[-1]
    tile = conv_tile_for(x.shape, w.shape, stride=stride, stream=stream,
                         n_buffers=n_buffers)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, c_pad), (0, 0)))
    return conv2d_int8_kernel(xl, w, tile=tile,
                              w_out=-(-x.shape[2] // stride), stride=stride,
                              stream=stream, n_buffers=n_buffers,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("stride", "stream", "n_buffers",
                                             "interpret"))
def dwconv_int8(x, w, *, stride: int = 1, stream: bool = False,
                n_buffers: int = 2, interpret: bool = False):
    """SAME depthwise (groups == C) conv, int8 in / int32 out, for
    HWIO-depthwise weights ``[k_h, k_w, 1, C]`` — the MobileNet path,
    with the same pinned/streamed weight tiers as the dense conv.  An
    entry point of its own, so that its kernel's device events carry
    this name and not ``conv2d_int8``'s.  Channels are zero-padded to
    the lane tiling and the padding is sliced off the result."""
    k_h, k_w = w.shape[:2]
    C = x.shape[-1]
    xl = to_line_layout(x, k_h, k_w, stride)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, xl.shape[-1] - C)))
    y = dwconv_int8_kernel(xl, w, w_out=-(-x.shape[2] // stride),
                           stride=stride, stream=stream,
                           n_buffers=n_buffers, interpret=interpret)
    return y[..., :C]


@functools.partial(jax.jit, static_argnames=("act_scale", "stride", "act",
                                             "stream", "n_buffers",
                                             "interpret"))
def conv2d_int8_requant(x, w, w_scale, bias, act_scale: float = 0.05, *,
                        stride: int = 1, act: str = "relu",
                        stream: bool = False, n_buffers: int = 2,
                        interpret: bool = False):
    """Full HPIPE layer engine: conv + per-channel dequant + bias +
    activation + requantize to int8 for the next engine (models/cnn.py
    contract)."""
    y = conv2d_int8(x, w, stride=stride, stream=stream, n_buffers=n_buffers,
                    interpret=interpret)
    y_q, _ = requant_epilogue(y, w_scale, bias, act_scale=act_scale,
                              act=act)
    return y_q
