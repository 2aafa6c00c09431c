"""Jit'd wrappers: SAME padding around the Pallas pooling kernels.

Padding geometry and the phase-split line layout come from the conv ops'
``to_line_layout`` (built on ``same_padded_width``) — the single source of
truth the compile-time VMEM accounting (``repro.compiler.engines``) also
derives line-buffer sizes from, so allocation and execution cannot drift
apart.  Maxpool pads with int8 -128 (the identity of max; SAME windows
always contain at least one real element, so padding never wins —
equivalent to the reference's +inf-under-min float padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv2d_int8.ops import to_line_layout
from repro.kernels.pool_int8.kernel import (global_avgpool_sum_kernel,
                                            maxpool_int8_kernel)


@functools.partial(jax.jit, static_argnames=("k", "stride", "interpret"))
def maxpool_int8(x, *, k: int, stride: int, interpret: bool = False):
    """SAME maxpool, int8 in / int8 out, via the line-buffer Pallas
    kernel.  x: [B, H, W, C] -> [B, ceil(H/s), ceil(W/s), C]."""
    C = x.shape[-1]
    xl = to_line_layout(x, k, k, stride, pad_value=-128)
    y = maxpool_int8_kernel(xl, k_h=k, k_w=k, stride=stride,
                            w_out=-(-x.shape[2] // stride),
                            interpret=interpret)
    return y[..., :C]


@functools.partial(jax.jit, static_argnames=("act_scale", "interpret"))
def global_avgpool_int8(x, *, act_scale: float = 0.05,
                        interpret: bool = False):
    """Global average pool + activation requantization, int8 in/out.
    x: [B, H, W, C] -> [B, 1, 1, C].  The kernel sums; the mean (sum /
    count, as ``jnp.mean`` divides) and the quantization are the
    reference's float ops, so kernel and reference cannot round apart."""
    B, H, W, C = x.shape
    s = global_avgpool_sum_kernel(x, interpret=interpret)
    m = s.astype(jnp.float32) / jnp.float32(H * W)
    return jnp.clip(jnp.round(m / act_scale), -127, 127).astype(jnp.int8)
