"""int8 conv2d — the HPIPE layer engine as a Pallas TPU kernel.

HPIPE computes a convolution row-by-row: a line buffer holds the k_h input
rows under the kernel's receptive field, the engine sweeps the full
activation width per cycle group, and weights are broadcast to the tensor
chains.  The TPU mapping:

  line buffer              -> VMEM scratch of the padded input rows under
                              one tile of output rows, refilled by an
                              explicit DMA per tile (activations stay in
                              the fast tier)
  full-width parallelism   -> each grid step computes one tile: ``bt``
                              images x ``r`` whole output rows, each tap
                              ONE MXU dot whose M dimension is
                              ``bt * r * w_pad`` (the width rounded up to
                              the sublane tiling; junk columns are
                              computed, never stored)
  int8 x int8 -> int32     -> jnp.dot with preferred_element_type=int32
                              (the AI-TB dot chains)

Grid: (B / bt, H_out / r), both axes in order: each step starts the DMA of
the NEXT step's line buffer into the other of two slots before it waits
for its own, so the fill overlaps the dots.  ``conv_tile`` picks
``(bt, r)`` from the layer's shapes, the weight tier and the batch under
an explicit VMEM budget.  The ops wrapper hands the kernel its input in
*line layout* (``ops.to_line_layout``): SAME-padded, width split into
``stride`` column phases, rows a whole number of ``stride``-row groups,
width and channels padded to the TPU's (8, 128) tiling —
``[B, H_pad, stride, W_phase, C_pad]``, viewed by the dense kernel as
``[B, H_pad / stride, stride, stride, W_phase, C_pad]``.  Tap (i, j) of a
stride-s conv then reads, for every output row of the tile, row phase
``i % s`` of consecutive row groups from ``i // s`` and column phase
``j % s`` from column ``j // s``: one contiguous slice (Mosaic refuses
strided int8 slices and loads).  The kernel has no boundary conditionals.

Two weight tiers, selected by the placement plan (core/schedule.py):

``_conv_kernel``         pinned: W delivered once into VMEM by the grid
                         pipeline (single-buffered — its block never
                         changes) and reused for every tile — the
                         on-chip M20K weight buffer.
``_conv_stream_kernel``  HBM-streamed: W stays in ``ANY`` (HBM) memory
                         space and its (i, j) tap blocks are DMA'd through
                         an ``n_buffers``-deep VMEM ring *once per tile*
                         — Eq. 2's "kernels are re-read once per output
                         line", with a tile of lines and images in place
                         of one line.  The ring is the last-stage FIFO;
                         reusing a slot only after its previous occupant
                         was consumed is the credit discipline of §V-A
                         (same pattern as ``stream_matmul_manual``).

The depthwise engine (``_dwconv_kernel``, ``_dwconv_stream_kernel``) keeps
one output row per grid step — grid (B, H_out), a k_h-row line buffer
(``fill_line_buffer``, ``line_buffer_taps``, shared with the maxpool
kernel) — and streams its [1, C] taps in ``DW_TAP_BURST``-tap bursts once
per output row.

Arithmetic stays on what the v5e executes: int8 x int8 -> int32 dots on
the MXU, and int32 (never int8) elementwise ops on the VPU.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import LANES, SUBLANES, round_up


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


#: output positions (images x rows x padded width) one grid step computes
#: at most: the M dimension of each tap's MXU dot.
TILE_M = 2048
#: VMEM the tile rule lets one grid step's working set claim.
TILE_VMEM_BUDGET = 24 << 20
#: Mosaic's scoped VMEM limit for the dense conv (the v5e default is
#: 16 MiB): the budget plus room for the compiler's own temporaries.
VMEM_LIMIT = 48 << 20


@dataclass(frozen=True)
class ConvTile:
    """One grid step of the dense conv: ``bt`` images x ``r`` output rows,
    each row computed ``w_pad`` columns wide (junk columns past ``w_out``
    are never stored).  ``vmem`` is the working set it claims."""

    bt: int
    r: int
    w_pad: int
    vmem: int


def _divisors(n: int):
    return [d for d in range(1, n + 1) if n % d == 0]


def conv_tile(*, batch: int, h_out: int, w_out: int, w_phase: int, c: int,
              c_out: int, k_h: int, k_w: int, stride: int, stream: bool,
              n_buffers: int) -> ConvTile:
    """The tile rule: the largest ``bt x r`` tile (``r`` divides ``h_out``,
    ``bt`` divides ``batch``, images stacked only once a tile covers the
    whole map) whose ``bt * r * w_pad`` stays within ``TILE_M`` and whose
    working set — the double-buffered line buffer, the pinned weights or
    the ring, the double-buffered int32 output block, the accumulator and
    one tap's product and operand — fits ``TILE_VMEM_BUDGET``.  ``c`` is
    the lane-padded input width of the line layout, ``w_phase`` its phase
    width.  One output row of one image is the floor."""
    w_pad = round_up(w_out, SUBLANES)
    c_out_lanes = round_up(c_out, LANES)
    taps = k_h * k_w
    weights = (min(n_buffers, taps) if stream else taps) * c * c_out_lanes

    def vmem(bt: int, r: int) -> int:
        m = bt * r * w_pad
        lines = 2 * bt * (r + (k_h - 1) // stride) * stride ** 2 * w_phase * c
        out = 2 * bt * r * w_pad * c_out_lanes * 4
        return lines + weights + out + 2 * m * c_out_lanes * 4 + m * c

    best = (1, 1)
    for r in _divisors(h_out):
        for bt in _divisors(batch) if r == h_out else (1,):
            if (bt * r * w_pad <= TILE_M and bt * r > best[0] * best[1]
                    and vmem(bt, r) <= TILE_VMEM_BUDGET):
                best = (bt, r)
    return ConvTile(bt=best[0], r=best[1], w_pad=w_pad, vmem=vmem(*best))


def tile_buffer(x_hbm_ref, lines, sems, *, bt: int, r: int):
    """Double-buffered line buffer of the dense conv, one DMA of a tile's
    ``r + (k_h - 1) // stride`` row groups (every row and column phase)
    of ``bt`` images per grid step.  The first step fetches its own tile;
    every step then starts the NEXT step's DMA into the other slot, and
    returns ``(slot, landed)``: this step's slot, and a callable that
    waits for the prefetch — called after the dots, so the fill overlaps
    them.  The last step prefetches its own tile again, so that every
    step runs the same code under a single conditional (a second one
    made every later operation of the kernel slower to trace, and
    tracing is set-up time).  The prefetch crosses grid steps, so both
    grid axes run in order."""
    # lax, not jnp operators, on the grid indices: jnp's dispatch per
    # operator cost more than the rest of the kernel's tracing
    b, i = pl.program_id(0), pl.program_id(1)
    n_rows = pl.num_programs(1)
    step = lax.add(lax.mul(b, n_rows), i)
    slot = lax.rem(step, 2)
    B, H_pad, stride, W_phase, C = x_hbm_ref.shape
    # rows as [row group, row phase]: a view of the line layout
    x_groups = x_hbm_ref.reshape(B, H_pad // stride, stride, stride,
                                 W_phase, C)

    def copy(n, slot):
        b, i = lax.div(n, n_rows), lax.rem(n, n_rows)
        return pltpu.make_async_copy(
            x_groups.at[pl.ds(lax.mul(b, bt), bt),
                        pl.ds(lax.mul(i, r), lines.shape[2])],
            lines.at[slot], sems.at[slot])

    @pl.when(lax.eq(step, 0))
    def _():
        first = copy(step, slot)
        first.start()
        first.wait()

    last = lax.sub(lax.mul(pl.num_programs(0), n_rows), 1)
    nxt = copy(lax.min(lax.add(step, 1), last), lax.sub(1, slot))
    nxt.start()
    return slot, nxt.wait


def tile_taps(lines, slot, *, k_h: int, k_w: int, stride: int, r: int,
              w_pad: int):
    """Yield ``(i, j, cols)``: the ``[bt * r * w_pad, C]`` MXU operand of
    tap (i, j) for the whole tile — ``r`` consecutive row groups from
    ``i // stride``, row phase ``i % stride``, column phase
    ``j % stride`` from column ``j // stride``: one contiguous load
    (Mosaic has no strided int8 load)."""
    bt, c = lines.shape[1], lines.shape[-1]
    for i in range(k_h):
        for j in range(k_w):
            cols = lines[slot, :, pl.ds(i // stride, r), i % stride,
                         j % stride, pl.ds(j // stride, w_pad), :]
            yield i, j, lax.reshape(cols, (bt * r * w_pad, c))


def _store_tile(o_ref, acc, *, w_pad: int):
    bt, r, w_out, c_out = o_ref.shape
    o_ref[...] = lax.slice(lax.reshape(acc, (bt, r, w_pad, c_out)),
                           (0, 0, 0, 0), (bt, r, w_out, c_out))


def _conv_kernel(x_hbm_ref, w_ref, o_ref, lines, sems, *,
                 k_h: int, k_w: int, stride: int, r: int, w_pad: int):
    slot, landed = tile_buffer(x_hbm_ref, lines, sems, bt=o_ref.shape[0],
                               r=r)
    acc = None
    for i, j, cols in tile_taps(lines, slot, k_h=k_h, k_w=k_w,
                                stride=stride, r=r, w_pad=w_pad):
        y = lax.dot(cols, w_ref[i, j],                    # [C, C_out]
                    preferred_element_type=jnp.int32)
        acc = y if acc is None else lax.add(acc, y)
    _store_tile(o_ref, acc, w_pad=w_pad)
    landed()


def _conv_stream_kernel(x_hbm_ref, w_hbm_ref, o_ref, lines, w_buf,
                        row_sems, w_sems, *, k_h: int, k_w: int,
                        stride: int, r: int, w_pad: int, n_buffers: int):
    """HBM-streamed weights: per tile the k_h*k_w weight taps flow
    HBM -> n_buffers-deep VMEM ring -> MACs, double-buffered so tap t+1's
    DMA overlaps tap t's compute."""
    slot, landed = tile_buffer(x_hbm_ref, lines, row_sems,
                               bt=o_ref.shape[0], r=r)

    n_taps = k_h * k_w
    nb = min(n_buffers, n_taps)

    def dma(t: int):
        i, j = divmod(t, k_w)
        return pltpu.make_async_copy(
            w_hbm_ref.at[i, j], w_buf.at[t % nb], w_sems.at[t % nb])

    # warm-up: fill the prefetch window (issue the address stream ahead)
    for t in range(nb):
        dma(t).start()

    acc = None
    taps = tile_taps(lines, slot, k_h=k_h, k_w=k_w, stride=stride, r=r,
                     w_pad=w_pad)
    for t, (_, _, cols) in enumerate(taps):
        dma(t).wait()                        # freeze until the burst lands
        y = lax.dot(cols, w_buf[t % nb], preferred_element_type=jnp.int32)
        acc = y if acc is None else lax.add(acc, y)
        if t + nb < n_taps:                  # dequeue returns the credit
            dma(t + nb).start()
    _store_tile(o_ref, acc, w_pad=w_pad)
    landed()


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


def conv2d_int8_kernel(x_lines, w, *, tile: ConvTile, w_out: int,
                       stride: int = 1, stream: bool = False,
                       n_buffers: int = 2, interpret: bool = False):
    """x_lines: [B, H_pad, stride, W_phase, C] int8 in line layout
    (``ops.to_line_layout``); ``w_out`` is the true output width (the
    phase width carries tiling padding).  w: [k_h, k_w, C, C_out] int8.
    ``tile`` is the layer's ``conv_tile``.  Returns [B, H_out, w_out,
    C_out] int32.

    ``stream=False`` pins W in VMEM for the whole sweep (on-chip tier);
    ``stream=True`` leaves W in HBM and re-reads it once per tile through
    an ``n_buffers``-deep double-buffer ring (HBM tier).
    """
    B, H_pad, phases, W_phase, C = x_lines.shape
    assert phases == stride, (x_lines.shape, stride)
    k_h, k_w, w_cin, w_cout = w.shape
    assert C == w_cin, (w.shape, C)
    H_out = (H_pad - k_h) // stride + 1
    assert (k_w - 1) // stride + tile.w_pad <= W_phase, (k_w, tile, W_phase)
    assert H_pad == stride * (H_out + (k_h - 1) // stride), (H_pad, k_h)
    bt, r = tile.bt, tile.r
    common = dict(k_h=k_h, k_w=k_w, stride=stride, r=r, w_pad=tile.w_pad)
    out_spec = pl.BlockSpec((bt, r, w_out, w_cout),
                            lambda b, i: (b, i, 0, 0))
    lines = pltpu.VMEM((2, bt, r + (k_h - 1) // stride, stride, stride,
                        W_phase, C), jnp.int8)
    kw = dict(
        grid=(B // bt, H_out // r),
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, H_out, w_out, w_cout), jnp.int32),
        interpret=interpret,
        # the line-buffer prefetch carries state from step to step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT))

    if not stream:
        return pl.pallas_call(
            functools.partial(_conv_kernel, **common),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # activations in HBM
                pl.BlockSpec(w.shape, lambda b, i: (0, 0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            scratch_shapes=[lines, pltpu.SemaphoreType.DMA((2,))],
            **kw,
        )(x_lines, w)

    nb = min(n_buffers, k_h * k_w)
    return pl.pallas_call(
        functools.partial(_conv_stream_kernel, n_buffers=nb, **common),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # activations in HBM
            pl.BlockSpec(memory_space=pl.ANY),      # weights STAY in HBM
        ],
        scratch_shapes=[
            lines,
            pltpu.VMEM((nb, C, w_cout), jnp.int8),  # the last-stage FIFO
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((nb,)),
        ],
        **kw,
    )(x_lines, w)


def dwconv_int8_kernel(x_lines, w, *, w_out: int, stride: int = 1,
                       stream: bool = False, n_buffers: int = 2,
                       interpret: bool = False):
    """The depthwise engine: grid (B, H_out), one output row per step.
    x_lines as for ``conv2d_int8_kernel``; w: [k_h, k_w, 1, C]
    HWIO-depthwise (the [1, C] tap rows broadcast across the output
    width; C_out == C).  Returns [B, H_out, w_out, C] int32; a streamed
    W is re-read once per output row."""
    B, H_pad, _, W_phase, C = x_lines.shape
    k_h, k_w, w_cin, w_cout = w.shape
    assert w_cin == 1 and C == w_cout, (w.shape, C)
    assert (k_w - 1) // stride + w_out <= W_phase, (k_w, w_out, W_phase)
    H_out = (H_pad - k_h) // stride + 1
    common = dict(k_h=k_h, k_w=k_w, stride=stride, w_out=w_out)
    kw = dict(
        grid=(B, H_out),
        out_specs=pl.BlockSpec((1, 1, w_out, C), lambda b, r: (b, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H_out, w_out, C), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")))
    line_buffer = pltpu.VMEM((k_h, stride, W_phase, C), jnp.int8)

    if not stream:
        return pl.pallas_call(
            functools.partial(_dwconv_kernel, **common),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # activations in HBM
                pl.BlockSpec(w.shape, lambda b, r: (0, 0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            scratch_shapes=[line_buffer, pltpu.SemaphoreType.DMA],
            **kw,
        )(x_lines, w)

    # [k_h, k_w, 1, C] -> [G, DW_TAP_BURST, C] tap bursts, zero-filled
    n_bursts = dw_tap_bursts(k_h, k_w)
    taps = w.reshape(k_h * k_w, C)
    w = jnp.pad(taps, ((0, n_bursts * DW_TAP_BURST - k_h * k_w), (0, 0))
                ).reshape(n_bursts, DW_TAP_BURST, C)
    nb = min(n_buffers, n_bursts)
    return pl.pallas_call(
        functools.partial(_dwconv_stream_kernel, n_buffers=nb, **common),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # activations in HBM
            pl.BlockSpec(memory_space=pl.ANY),      # weights STAY in HBM
        ],
        scratch_shapes=[
            line_buffer,
            pltpu.VMEM((nb, DW_TAP_BURST, C), jnp.int8),  # one burst a slot
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((nb,)),
        ],
        **kw,
    )(x_lines, w)
