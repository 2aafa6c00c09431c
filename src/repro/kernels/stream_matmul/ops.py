"""Jit'd public wrappers for the streamed-weight matmul.

``stream_matmul(x, w, mode=...)``:
  mode="stream"  grid-pipelined K-block streaming (auto double-buffer)
  mode="fifo"    explicit n_buffers-deep prefetch ring (credit semantics)
  mode="pinned"  whole W resident in VMEM for the call (on-chip tier):
                 single K step, W delivered via the grid pipeline once.

The placement plan (core/streaming.plan_vmem_residency) chooses the mode
per weight tensor; ``ops`` is the seam where that decision becomes a
kernel configuration, the way the H2PIPE compiler instantiates either an
on-chip weight buffer or an HBM FIFO chain per layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pallas_compat import LANES, round_up
from repro.kernels.stream_matmul.kernel import (stream_matmul_kernel,
                                                stream_matmul_manual)


def _fifo_width(N: int, bn: int):
    """``(padded N, N block)`` of the fifo ring: it DMAs [bk, bn] slices
    of W out of HBM, and Mosaic slices HBM only in whole lane tiles, so
    an N off the 128 grid (1000 classes) streams as one zero-padded
    full-width block, its extra columns sliced off the result."""
    bn = min(bn, N)
    if N % LANES == 0 or bn != N:
        return N, bn
    n_padded = round_up(N, LANES)
    return n_padded, n_padded


@functools.partial(jax.jit, static_argnames=("mode", "bm", "bk", "bn",
                                             "n_buffers", "interpret"))
def stream_matmul(x, w, *, mode: str = "stream", bm: int = 128,
                  bk: int = 512, bn: int = 128, n_buffers: int = 2,
                  interpret: bool = False):
    if mode == "pinned":
        # whole-W VMEM residency: one K block spanning all of K
        return stream_matmul_kernel(x, w, bm=bm, bk=w.shape[0], bn=bn,
                                    interpret=interpret)
    if mode == "stream":
        return stream_matmul_kernel(x, w, bm=bm, bk=bk, bn=bn,
                                    interpret=interpret)
    if mode == "fifo":
        N = w.shape[1]
        n_padded, bn = _fifo_width(N, bn)
        w = jnp.pad(w, ((0, 0), (0, n_padded - N)))
        y = stream_matmul_manual(x, w, bm=bm, bk=bk, bn=bn,
                                 n_buffers=n_buffers, interpret=interpret)
        return y[:, :N]
    raise ValueError(f"unknown mode {mode!r}")


def vmem_bytes(mode: str, M: int, K: int, N: int, dtype_bytes: int, *,
               bm: int = 128, bk: int = 512, bn: int = 128,
               n_buffers: int = 2) -> int:
    """VMEM working set the call claims — the M20K-cost analogue that the
    placement planner charges per decision (Eq. 1's '-2' term)."""
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    if mode == "pinned":
        bk = K                                   # one K block: all of W's rows
    # the grid pipeline double-buffers every blocked operand; the fifo
    # variant holds W in its own n_buffers-deep ring instead
    x_b = 2 * bm * (K if mode == "fifo" else bk) * dtype_bytes
    if mode == "fifo":
        w_b = n_buffers * bk * _fifo_width(N, bn)[1] * dtype_bytes
    else:
        w_b = 2 * bk * bn * dtype_bytes
    o_b = 2 * bm * bn * 4
    acc_b = 0 if mode == "fifo" else bm * bn * 4     # K-loop scratch
    return x_b + w_b + o_b + acc_b
