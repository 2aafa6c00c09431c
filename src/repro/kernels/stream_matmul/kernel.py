"""Streamed-weight matmul — the H2PIPE weight path as a Pallas TPU kernel.

The paper keeps compute units fed from HBM by (a) issuing weight reads
hundreds of cycles ahead (the address stream is deterministic), (b) deep
burst-matching + last-stage FIFOs sized from the measured worst-case
latency, and (c) credit-based flow control bounding the in-flight words.
On TPU the same design maps to:

  burst length      -> K-block depth of each HBM->VMEM DMA (``bk``)
  last-stage FIFO   -> multi-buffered VMEM scratch (``n_buffers`` slots)
  credit counter    -> the bounded in-flight DMA window: a slot's DMA is
                       issued only after its previous occupant is consumed
                       (wait) — exactly "credits returned on dequeue"
  freeze signal     -> the implicit stall of ``.wait()`` when a buffer has
                       not landed — the grid stalls, nothing else does

Two implementations:

``stream_matmul_kernel``  grid-pipelined: BlockSpec index maps stream X and
    W blocks; the Pallas pipeline double-buffers the HBM->VMEM DMAs
    automatically (n_buffers = 2, fixed).

``stream_matmul_manual``  explicit-FIFO: W stays in ``ANY`` (HBM) memory
    space; the kernel issues its own ``pltpu.make_async_copy`` per K-block
    into an ``n_buffers``-deep VMEM scratch ring with per-slot DMA
    semaphores.  ``n_buffers`` is the paper's FIFO-depth knob — benchmarks
    sweep it like Table II sweeps burst length.

Both accumulate over the K grid dimension in scratch — f32 for float
inputs, exact int32 for int8 inputs (the MXU contract) — and support a
``pinned`` mode in the ops wrapper (whole W resident in VMEM: the paper's
on-chip weight buffer).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# grid-pipelined version (Pallas auto double-buffering)
# ---------------------------------------------------------------------------


def _acc_dtype(out_dtype):
    """int8 inputs accumulate exactly in int32 (the MXU contract and the
    bit-identity guarantee for wide fc heads: sums exceed f32's 2^24);
    float inputs accumulate in f32."""
    return jnp.int32 if out_dtype == jnp.int32 else jnp.float32


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def stream_matmul_kernel(x, w, *, bm: int = 128, bk: int = 512,
                         bn: int = 128, interpret: bool = False):
    """x: [M, K] @ w: [K, N] -> [M, N].  W blocks stream HBM->VMEM once per
    (m-block, k-block) grid step; ``bk`` is the burst-length analogue."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (x.shape, w.shape)
    nm, nk, nn = M // bm, K // bk, N // bn
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    if out_dtype == jnp.int8:
        out_dtype = jnp.int32
    grid = (nm, nn, nk)
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), _acc_dtype(out_dtype))],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, w)


# ---------------------------------------------------------------------------
# explicit-FIFO version (manual DMA ring, credit semantics)
# ---------------------------------------------------------------------------


def _mm_manual_kernel(x_ref, w_hbm_ref, o_ref, w_buf, sems, *,
                      nk: int, n_buffers: int, bk: int, bn: int):
    """One (m, n) output block; K-loop with an ``n_buffers``-deep prefetch
    ring over W K-blocks living in HBM.

    Credit discipline: slot s may hold only one outstanding DMA; issuing
    for k requires the consumer to have drained k - n_buffers (same slot) —
    the in-flight window never exceeds n_buffers bursts, so VMEM (the
    paper's FIFO) cannot be overrun and no deadlock is possible.
    """
    n = pl.program_id(1)

    def dma(k, slot):
        return pltpu.make_async_copy(
            w_hbm_ref.at[pl.ds(k * bk, bk), pl.ds(n * bn, bn)],
            w_buf.at[slot], sems.at[slot])

    # warm-up: fill the prefetch window (the paper's "run the address
    # generator hundreds of cycles ahead")
    for s in range(min(n_buffers, nk)):
        dma(s, s).start()

    acc_dtype = _acc_dtype(o_ref.dtype)

    def body(k, acc):
        slot = jax.lax.rem(k, n_buffers)
        dma(k, slot).wait()                            # freeze until landed
        xk = x_ref[:, pl.ds(pl.multiple_of(k * bk, bk), bk)]
        acc = acc + jnp.dot(xk, w_buf[slot],
                            preferred_element_type=acc_dtype)
        # dequeue returns the credit: reuse the slot for k + n_buffers
        nxt = k + n_buffers

        @pl.when(nxt < nk)
        def _():
            dma(nxt, slot).start()
        return acc

    acc = jax.lax.fori_loop(
        0, nk, body, jnp.zeros(o_ref.shape, acc_dtype))
    o_ref[...] = acc.astype(o_ref.dtype)


def stream_matmul_manual(x, w, *, bm: int = 128, bk: int = 512,
                         bn: int = 128, n_buffers: int = 2,
                         interpret: bool = False):
    """Explicit prefetch-ring variant; W never enters the grid pipeline —
    it stays in HBM (memory_space=ANY) and the kernel DMAs K-blocks itself.
    ``n_buffers`` == the paper's FIFO depth / credit count."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    nm, nk, nn = M // bm, K // bk, N // bn
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    if out_dtype == jnp.int8:
        out_dtype = jnp.int32
    grid = (nm, nn)
    return pl.pallas_call(
        functools.partial(_mm_manual_kernel, nk=nk, n_buffers=n_buffers,
                          bk=bk, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda m, n: (m, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # W stays in HBM
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((n_buffers, bk, bn), w.dtype),
            pltpu.SemaphoreType.DMA((n_buffers,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x, w)
