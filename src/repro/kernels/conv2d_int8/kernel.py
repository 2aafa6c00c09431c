"""int8 conv2d — the HPIPE layer engine as a Pallas TPU kernel.

HPIPE computes a convolution row-by-row: a line buffer holds the k_h input
rows under the kernel's receptive field, the engine sweeps the full
activation width per cycle group, and weights are broadcast to the tensor
chains.  The TPU mapping:

  line buffer (k_h rows)   -> VMEM scratch of k_h padded input rows,
                              refilled by an explicit DMA per output row
                              (the sliding window never holds more than
                              k_h rows — activations stay in the fast tier)
  full-width parallelism   -> each grid step computes one whole output row;
                              the W_out dim rides the MXU/VPU lanes
  int8 x int8 -> int32     -> jnp.dot with preferred_element_type=int32
                              (the AI-TB dot chains)

Grid: (B, H_out).  The ops wrapper hands the kernel its input in *line
layout* (``ops.to_line_layout``): SAME-padded, width split into ``stride``
column phases, width and channels padded to the TPU's (8, 128) tiling —
``[B, H_pad, stride, W_phase, C_pad]``.  Tap column ``j`` of a stride-s
conv then reads phase ``j % s`` at offset ``j // s`` as one contiguous
slice (Mosaic refuses strided int8 slices and loads), and the per-row DMA
moves whole tiles.  The kernel has no boundary conditionals.

Two weight tiers, selected by the placement plan (core/schedule.py):

``_conv_kernel``         pinned: W delivered once into VMEM by the grid
                         pipeline (single-buffered — its block never
                         changes) and reused for every output row — the
                         on-chip M20K weight buffer.
``_conv_stream_kernel``  HBM-streamed: W stays in ``ANY`` (HBM) memory
                         space and its (i, j) tap blocks are DMA'd through
                         an ``n_buffers``-deep VMEM ring *once per output
                         row* — Eq. 2's "kernels are re-read once per
                         output line".  The ring is the last-stage FIFO;
                         reusing a slot only after its previous occupant
                         was consumed is the credit discipline of §V-A
                         (same pattern as ``stream_matmul_manual``).
                         Depthwise taps ([1, C]) move in
                         ``DW_TAP_BURST``-tap bursts.

Arithmetic stays on what the v5e executes: int8 x int8 -> int32 dots on
the MXU, and int32 (never int8) elementwise ops on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fill_line_buffer(x_hbm_ref, rows_buf, sem, *, k_h: int, stride: int):
    """DMA the k_h input rows (all column phases) for this (batch,
    output-row) grid step."""
    b = pl.program_id(0)
    r = pl.program_id(1)
    cp = pltpu.make_async_copy(
        x_hbm_ref.at[b, pl.ds(r * stride, k_h)], rows_buf, sem)
    cp.start()
    cp.wait()


def line_buffer_taps(rows_buf, *, k_h: int, k_w: int, stride: int,
                     w_out: int):
    """Yield ``(i, j, cols)``: the [w_out, C] input columns under tap
    (i, j), a contiguous slice of the line buffer's phase ``j % stride``
    (each phase row is loaded once per kernel row)."""
    for i in range(k_h):
        phases = [rows_buf[i, p] for p in range(stride)]   # [W_phase, C]
        for j in range(k_w):
            off = j // stride
            yield i, j, phases[j % stride][off:off + w_out]


def _conv_kernel(x_hbm_ref, w_ref, o_ref, rows_buf, sem, *,
                 k_h: int, k_w: int, stride: int, w_out: int):
    fill_line_buffer(x_hbm_ref, rows_buf, sem, k_h=k_h, stride=stride)
    acc = jnp.zeros((w_out, o_ref.shape[-1]), jnp.int32)
    for i, j, cols in line_buffer_taps(rows_buf, k_h=k_h, k_w=k_w,
                                       stride=stride, w_out=w_out):
        acc = acc + jnp.dot(cols, w_ref[i, j],            # [C, C_out]
                            preferred_element_type=jnp.int32)
    o_ref[0, 0] = acc


def _conv_stream_kernel(x_hbm_ref, w_hbm_ref, o_ref, rows_buf, w_buf,
                        row_sem, w_sems, *, k_h: int, k_w: int, stride: int,
                        w_out: int, n_buffers: int):
    """HBM-streamed weights: per output row the k_h*k_w weight taps flow
    HBM -> n_buffers-deep VMEM ring -> MACs, double-buffered so tap t+1's
    DMA overlaps tap t's compute."""
    fill_line_buffer(x_hbm_ref, rows_buf, row_sem, k_h=k_h, stride=stride)

    n_taps = k_h * k_w
    nb = min(n_buffers, n_taps)

    def dma(t: int):
        i, j = divmod(t, k_w)
        return pltpu.make_async_copy(
            w_hbm_ref.at[i, j], w_buf.at[t % nb], w_sems.at[t % nb])

    # warm-up: fill the prefetch window (issue the address stream ahead)
    for t in range(nb):
        dma(t).start()

    acc = jnp.zeros((w_out, o_ref.shape[-1]), jnp.int32)
    taps = line_buffer_taps(rows_buf, k_h=k_h, k_w=k_w, stride=stride,
                            w_out=w_out)
    for t, (_, _, cols) in enumerate(taps):
        dma(t).wait()                        # freeze until the burst lands
        acc = acc + jnp.dot(cols, w_buf[t % nb],
                            preferred_element_type=jnp.int32)
        if t + nb < n_taps:                  # dequeue returns the credit
            dma(t + nb).start()
    o_ref[0, 0] = acc


def _dwconv_kernel(x_hbm_ref, w_ref, o_ref, rows_buf, sem, *,
                   k_h: int, k_w: int, stride: int, w_out: int):
    """Depthwise (grouped, groups == C) variant of ``_conv_kernel``: each
    channel convolves with its own k_h x k_w filter, so the tap MAC is an
    elementwise VPU multiply against a broadcast [1, C] weight row instead
    of an MXU dot — the per-channel tensor chains of a MobileNet engine."""
    fill_line_buffer(x_hbm_ref, rows_buf, sem, k_h=k_h, stride=stride)
    acc = jnp.zeros((w_out, o_ref.shape[-1]), jnp.int32)
    for i, j, cols in line_buffer_taps(rows_buf, k_h=k_h, k_w=k_w,
                                       stride=stride, w_out=w_out):
        acc = acc + cols.astype(jnp.int32) * w_ref[i, j].astype(jnp.int32)
    o_ref[0, 0] = acc


#: taps per streamed depthwise DMA: one [1, C] int8 tap is narrower than
#: the int8 HBM tiling's 4-row sublane group, which Mosaic refuses to
#: slice, so taps stream in bursts of this many rows.
DW_TAP_BURST = 4


def dw_tap_bursts(k_h: int, k_w: int) -> int:
    """How many ``DW_TAP_BURST``-tap bursts hold a k_h x k_w filter."""
    return -(-(k_h * k_w) // DW_TAP_BURST)


def _dwconv_stream_kernel(x_hbm_ref, w_hbm_ref, o_ref, rows_buf, w_buf,
                          row_sem, w_sems, *, k_h: int, k_w: int,
                          stride: int, w_out: int, n_buffers: int):
    """HBM-streamed depthwise: the [1, C] weight taps, packed into
    ``DW_TAP_BURST``-row bursts ([G, DW_TAP_BURST, C], zero-filled past
    the last tap), flow through the same n_buffers-deep VMEM ring / credit
    discipline as ``_conv_stream_kernel``, re-read once per output row
    (Eq. 2)."""
    fill_line_buffer(x_hbm_ref, rows_buf, row_sem, k_h=k_h, stride=stride)

    n_bursts = w_hbm_ref.shape[0]
    nb = min(n_buffers, n_bursts)

    def dma(g: int):
        return pltpu.make_async_copy(
            w_hbm_ref.at[g], w_buf.at[g % nb], w_sems.at[g % nb])

    for g in range(nb):
        dma(g).start()

    acc = jnp.zeros((w_out, o_ref.shape[-1]), jnp.int32)
    burst = None
    taps = line_buffer_taps(rows_buf, k_h=k_h, k_w=k_w, stride=stride,
                            w_out=w_out)
    for t, (_, _, cols) in enumerate(taps):
        g, r = divmod(t, DW_TAP_BURST)
        if r == 0:
            dma(g).wait()
            burst = w_buf[g % nb].astype(jnp.int32)      # [BURST, C]
            if g + nb < n_bursts:                        # credit returned
                dma(g + nb).start()
        acc = acc + cols.astype(jnp.int32) * burst[r:r + 1]
    o_ref[0, 0] = acc


def conv2d_int8_kernel(x_lines, w, *, w_out: int, stride: int = 1,
                       stream: bool = False, n_buffers: int = 2,
                       depthwise: bool = False, interpret: bool = False):
    """x_lines: [B, H_pad, stride, W_phase, C] int8 in line layout
    (``ops.to_line_layout``); ``w_out`` is the true output width (the
    phase width carries tiling padding).  w: [k_h, k_w, C, C_out] int8 —
    or [k_h, k_w, 1, C] HWIO-depthwise when ``depthwise=True`` (the
    [1, C] tap rows broadcast across the output width; C_out == C).
    Returns [B, H_out, w_out, C_out] int32.

    ``stream=False`` pins W in VMEM for the whole row sweep (on-chip tier);
    ``stream=True`` leaves W in HBM and re-reads it once per output row
    through an ``n_buffers``-deep double-buffer ring (HBM tier).
    """
    B, H_pad, phases, W_phase, C = x_lines.shape
    assert phases == stride, (x_lines.shape, stride)
    k_h, k_w, w_cin, w_cout = w.shape
    assert (k_w - 1) // stride + w_out <= W_phase, (k_w, w_out, W_phase)
    if depthwise:
        assert w_cin == 1 and C == w_cout, (w.shape, C)
        C_out = C
        body, stream_body = _dwconv_kernel, _dwconv_stream_kernel
        ring_tap = (DW_TAP_BURST, C)            # one tap burst per slot
    else:
        assert C == w_cin, (w.shape, C)
        C_out = w_cout
        body, stream_body = _conv_kernel, _conv_stream_kernel
        ring_tap = (C, C_out)                   # one [C, C_out] tap per slot
    H_out = (H_pad - k_h) // stride + 1
    grid = (B, H_out)
    common = dict(k_h=k_h, k_w=k_w, stride=stride, w_out=w_out)
    out_spec = pl.BlockSpec((1, 1, w_out, C_out), lambda b, r: (b, r, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, H_out, w_out, C_out), jnp.int32)
    line_buffer = pltpu.VMEM((k_h, stride, W_phase, C), jnp.int8)
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    if not stream:
        return pl.pallas_call(
            functools.partial(body, **common),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # activations in HBM
                pl.BlockSpec((k_h, k_w, w_cin, w_cout),
                             lambda b, r: (0, 0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=[
                line_buffer,
                pltpu.SemaphoreType.DMA,
            ],
            interpret=interpret,
            compiler_params=compiler_params,
        )(x_lines, w)

    if depthwise:
        # [k_h, k_w, 1, C] -> [G, DW_TAP_BURST, C] tap bursts, zero-filled
        n_bursts = dw_tap_bursts(k_h, k_w)
        taps = w.reshape(k_h * k_w, C)
        w = jnp.pad(taps, ((0, n_bursts * DW_TAP_BURST - k_h * k_w), (0, 0))
                    ).reshape(n_bursts, DW_TAP_BURST, C)
        nb = min(n_buffers, n_bursts)
    else:
        nb = min(n_buffers, k_h * k_w)
    return pl.pallas_call(
        functools.partial(stream_body, n_buffers=nb, **common),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # activations in HBM
            pl.BlockSpec(memory_space=pl.ANY),      # weights STAY in HBM
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            line_buffer,
            pltpu.VMEM((nb,) + ring_tap, jnp.int8),  # the last-stage FIFO
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((nb,)),
        ],
        interpret=interpret,
        compiler_params=compiler_params,
    )(x_lines, w)
