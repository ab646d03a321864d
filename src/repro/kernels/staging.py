"""Memory-space-aware strip-DMA staging engine for the fused ConvDK kernels.

The paper's dataflow claim is about *buffer movement*: input strips stream
through on-chip memory with maximal halo reuse, and the strip loads are the
only input-side traffic.  The first fused renderings of our kernels cheated
on that point — their BlockSpecs kept the full padded height of a channel
block VMEM-resident and carved strips out of it with ``pl.ds``, which is
interpret-friendly but (a) refetches the whole padded height every time the
channel block advances and (b) never exercises the strip-by-strip DMA
structure the traffic model (``core.perfmodel``) prices.

This module is the shared production rendering.  One engine serves every
fused pipeline (separable, MBConv pass 1, both MBConv pass-2 variants,
their sharded wrappers) under a three-mode **residency** axis:

* ``"resident"`` — the whole frame of one (batch, channel block) lives
  in VMEM and windows are ``pl.ds`` views into it.  The frame is copied
  once per (batch, channel-block) pair and prefetched one pair ahead
  into a second slot — what a BlockSpec pipeline would do, but with the
  halo built in VMEM.  Cheapest when the whole (channel-block of the)
  input fits VMEM and the channel grid has one block; priced honestly by
  the ``resident`` traffic model.  A non-overlapping resident stream
  (the retained-DW re-read) is a plain per-strip BlockSpec block.
* ``"strip_dma"`` — the input lives in the ``ANY``/HBM memory space; each
  grid cell issues one async copy of exactly its halo'd strip window into
  a single VMEM scratch slot and waits on it before computing.  HBM words
  = the strip-staging accounting (halo rows re-read, never re-written).
* ``"strip_dma_db"`` — same windows, **double-buffered**: two scratch
  slots + two DMA semaphores; each cell prefetches the *next* grid cell's
  window while computing its own, so the strip stream pipelines behind
  compute.  Identical HBM words to ``strip_dma`` (double-buffering buys
  overlap, not traffic) at 2x the strip scratch.

In every mode the source is the block's activation as the previous
block wrote it: unpadded spatially, channels in whole 128-lane groups.
The conv halo (SAME padding, the last strip's height cover, the width
cover of whole sublanes) exists only in VMEM: each copy lands the
source's pixels at their place in the slot and the slot's halo is zero.
Mosaic DMAs a strip only out of an array whose channel dim is a whole
number of 128-lane groups, which is why the lane layout and the VMEM
halo go together (``common.in_lane_layout``).

The engine's unit of work is a **window**: the (batch, row-strip,
channel-block) triple one grid cell stages.  ``StripPlan`` carries the
static geometry plus the kernel's grid so the stream can (1) flatten the
grid cell into a linear DMA-stream step and (2) decode step+1 back into
the *next* cell's window coordinates for prefetch — the grid's iteration
order IS the DMA stream order, whatever dims (c_out blocks, c_mid
reduction, ...) interleave between strips.

Everything here runs identically under interpret mode: the pallas
interpreter implements the copy/semaphore primitives, so CPU parity tests
execute the same DMA-structured code path as a real TPU launch.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import pallas_async_copy
from ..core import telemetry
from ..core.perfmodel import (
    DEFAULT_RESIDENCY,
    RESIDENCY_MODES,
    staging_slots,
    validate_residency,
)

__all__ = [
    "DEFAULT_RESIDENCY",
    "RESIDENCY_MODES",
    "StripPlan",
    "StripStream",
    "strip_plan",
]


@dataclasses.dataclass(frozen=True)
class StripPlan:
    """Static description of one staged input stream of a fused kernel.

    Geometry (one window is ``(in_rows, w_tot, c_block)``):

    * ``h_tot`` / ``w_tot`` — rows / width of the padded frame the
      windows cut: the conv's SAME padding, the height cover of the last
      strip, and the reach of the launched output width in whole
      sublanes (``common.launch_geometry``).  A window spans the whole
      frame width.
    * ``h_src`` / ``w_src`` / ``top`` / ``left`` — where the source
      tensor's pixels sit in that frame: its rows land at frame row
      ``top``, its columns at frame column ``left``.  The source is the
      block's activation itself; the frame around it (the conv halo) is
      built in VMEM, zero, and no padded copy of the source exists in
      HBM.  0 for ``h_src`` / ``w_src`` means the source IS the frame
      (the retained-DW re-read).
    * ``c_block`` — channel lanes per window (a multiple of 128; the
      source's channel dim is a whole number of lane groups).
    * ``tile_h`` / ``stride`` / ``k_h`` — strip geometry; ``k_h == 1,
      stride == 1`` describes a non-overlapping row-block stream (the
      retained-DW re-read), anything else a halo'd conv stream.

    Stream structure:

    * ``grid`` — the pallas grid, iteration order; its flattened index is
      the DMA-stream step.
    * ``window_dims`` — which grid dims select (batch, row-strip,
      channel-block) of a cell's window.
    """

    h_tot: int
    w_tot: int
    c_block: int
    tile_h: int
    grid: Tuple[int, ...]
    window_dims: Tuple[int, int, int]
    stride: int = 1
    k_h: int = 1
    residency: str = DEFAULT_RESIDENCY
    prefetch_priority: Optional[int] = None   # DMA stream priority for
    #                                           prefetches (None = default;
    #                                           dropped where unsupported)
    h_src: int = 0
    w_src: int = 0
    top: int = 0
    left: int = 0

    def __post_init__(self):
        validate_residency(self.residency)
        assert len(self.window_dims) == 3 and all(
            0 <= d < len(self.grid) for d in self.window_dims), self
        if not self.h_src:
            object.__setattr__(self, "h_src", self.h_tot)
        if not self.w_src:
            object.__setattr__(self, "w_src", self.w_tot)
        assert self.top + self.h_src <= self.h_tot, self
        assert self.left + self.w_src <= self.w_tot, self

    @property
    def in_rows(self) -> int:
        """Rows per halo'd window (``tile_h`` when non-overlapping)."""
        return (self.tile_h - 1) * self.stride + self.k_h

    @property
    def is_dma(self) -> bool:
        """Whether the stream moves strip windows (not whole images)."""
        return self.residency != "resident"

    @property
    def halo(self) -> bool:
        """Whether consecutive windows overlap (conv-style strips)."""
        return self.k_h > 1 or self.stride > 1

    @property
    def staged(self) -> bool:
        """Whether the engine stages the stream into its own VMEM slots.
        Only a resident non-overlapping stream (the retained-DW re-read)
        is a plain BlockSpec block instead."""
        return self.is_dma or self.halo

    @property
    def slot_rows(self) -> int:
        """Rows per VMEM slot: a strip window, or (``resident``) the whole
        frame of one channel block."""
        return self.in_rows if self.is_dma else self.h_tot

    @property
    def n_slots(self) -> int:
        return staging_slots(self.residency) if self.is_dma else 2

    @property
    def n_steps(self) -> int:
        return math.prod(self.grid)

    @property
    def fetch_period(self) -> int:
        """Grid steps per fetch of a ``resident`` frame: the frame changes
        when its (batch, channel-block) pair does, which is whenever the
        innermost of those dims with more than one index moves."""
        bd, _, cd = self.window_dims
        moving = [d for d in (bd, cd) if self.grid[d] > 1]
        if not moving:
            return self.n_steps
        return math.prod(self.grid[max(moving) + 1:])

    def strip_copies(self):
        """``(edges, interior)`` of the strip windows' copies out of the
        source.  ``edges`` lists ``(strip, src_row, rows, slot_row)`` for
        each strip whose window crosses the source's top or bottom edge:
        ``rows`` source rows from ``src_row`` land at window row
        ``slot_row``, the rest of the window is halo.  ``interior`` is the
        ``(first, last)`` strip whose window lies wholly inside the source
        (one copy shape, at a row offset that moves with the strip), or
        None."""
        n_th = self.grid[self.window_dims[1]]
        edges, inner = [], []
        for ti in range(n_th):
            r = ti * self.tile_h * self.stride - self.top
            if r >= 0 and r + self.in_rows <= self.h_src:
                inner.append(ti)
                continue
            lo, hi = max(0, r), min(self.h_src, r + self.in_rows)
            assert hi > lo, (self, ti)
            edges.append((ti, lo, hi - lo, lo - r))
        return tuple(edges), ((inner[0], inner[-1]) if inner else None)

    # -- launch-side helpers -------------------------------------------------

    def in_spec(self, index_map=None) -> pl.BlockSpec:
        """BlockSpec for the staged input.

        A staged stream's ref arrives un-blocked in the ANY space and the
        engine carves windows itself.  ``index_map`` maps grid indices to
        the per-strip block position of a resident non-overlapping
        stream, the one stream that stays a BlockSpec block.
        """
        if self.staged:
            return pl.BlockSpec(memory_space=pl.ANY)
        return pl.BlockSpec((1, self.tile_h, self.w_tot, self.c_block),
                            index_map)

    def scratch_shapes(self, dtype) -> tuple:
        """Engine scratch to append to the kernel's ``scratch_shapes``:
        the slot buffer plus the per-slot DMA semaphore array.  Empty for
        a BlockSpec-blocked stream."""
        if not self.staged:
            return ()
        return (pltpu.VMEM((self.n_slots, self.slot_rows, self.w_tot,
                            self.c_block), dtype),
                pltpu.SemaphoreType.DMA((self.n_slots,)))

    def take_scratch(self, scratch: tuple) -> tuple:
        """Split a kernel's trailing scratch refs: (engine_refs, rest)."""
        n = 2 if self.staged else 0
        return (scratch[len(scratch) - n:] if n else (),
                scratch[:len(scratch) - n])


def strip_plan(
    *,
    h_tot: int,
    w_tot: int,
    c_block: int,
    tile_h: int,
    grid: Tuple[int, ...],
    window_dims: Tuple[int, int, int],
    stride: int = 1,
    k_h: int = 1,
    residency: Optional[str] = None,
    prefetch_priority: Optional[int] = None,
    h_src: int = 0,
    w_src: int = 0,
    top: int = 0,
    left: int = 0,
) -> StripPlan:
    """``StripPlan`` constructor with the engine-wide residency default.

    Building a plan is trace-time work, so the telemetry hooks here tick
    once per kernel BUILD (per compilation), not per execution: a plan's
    stream geometry fully determines its issue count and staged words, so
    counting at construction is both cheap and exact."""
    plan = StripPlan(
        h_tot=h_tot, w_tot=w_tot, c_block=c_block,
        tile_h=tile_h, grid=tuple(grid), window_dims=tuple(window_dims),
        stride=stride, k_h=k_h,
        residency=DEFAULT_RESIDENCY if residency is None else residency,
        prefetch_priority=prefetch_priority, h_src=h_src, w_src=w_src,
        top=top, left=left)
    telemetry.counter("staging.plans")
    if plan.is_dma:
        telemetry.counter("staging.dma_issues", plan.n_steps)
        telemetry.counter(
            "staging.window_words",
            plan.n_steps * plan.in_rows * plan.w_tot * plan.c_block)
    return plan


class StripStream:
    """Per-grid-cell view of one staged input stream (kernel-side).

    Construct inside the kernel body from the plan, the input ref and the
    engine's scratch refs, then call :meth:`get` once to obtain the
    ``(in_rows, w_tot, c_block)`` window of this cell — staged per the
    plan's residency (blocking DMA, double-buffered DMA with next-window
    prefetch, or a view into a double-buffered whole frame).

    Every copy lands the source's rows and columns at their place in the
    frame; the frame's halo is zero in VMEM.  The halo columns, and a
    resident frame's halo rows, are never written by a copy, so they are
    zeroed once, at the first grid step, before any copy starts.  A strip
    window that crosses the source's top or bottom edge zeroes its halo
    rows just before its copy starts: the slot held another window before.
    """

    def __init__(self, plan: StripPlan, x_ref, stage_refs: tuple):
        self.plan = plan
        self.x_ref = x_ref
        if plan.staged:
            self.buf, self.sem = stage_refs
        else:
            assert not stage_refs, stage_refs
            self.buf = self.sem = None

    # -- stream arithmetic ---------------------------------------------------

    def _step(self):
        """Flattened grid-cell index — the DMA-stream step."""
        step = pl.program_id(0)
        for d in range(1, len(self.plan.grid)):
            step = step * self.plan.grid[d] + pl.program_id(d)
        return step

    def _window_at(self, step):
        """Decode a step into its window's (batch, strip, chan) indices."""
        sizes = self.plan.grid
        idx = [None] * len(sizes)
        rem = step
        for d in reversed(range(len(sizes))):
            idx[d] = rem % sizes[d]
            rem = rem // sizes[d]
        bd, sd, cd = self.plan.window_dims
        return idx[bd], idx[sd], idx[cd]

    def _window_here(self):
        bd, sd, cd = self.plan.window_dims
        return pl.program_id(bd), pl.program_id(sd), pl.program_id(cd)

    # -- the frame's halo ----------------------------------------------------

    def _zero(self, slot, row, rows, col, cols):
        if rows > 0 and cols > 0:
            self.buf[slot, pl.ds(row, rows), pl.ds(col, cols), :] = jnp.zeros(
                (rows, cols, self.plan.c_block), self.buf.dtype)

    def _zero_fixed_halo(self):
        """Zero what no copy ever writes: the halo columns of every slot,
        and a resident frame's halo rows."""
        p = self.plan
        right = p.left + p.w_src
        for slot in range(p.n_slots):
            self._zero(slot, 0, p.slot_rows, 0, p.left)
            self._zero(slot, 0, p.slot_rows, right, p.w_tot - right)
            if not p.is_dma:
                self._zero(slot, 0, p.top, 0, p.w_tot)
                bottom = p.top + p.h_src
                self._zero(slot, bottom, p.h_tot - bottom, 0, p.w_tot)

    # -- DMA issue -----------------------------------------------------------

    def _copy(self, src_row, rows, slot, slot_row, bi, ci):
        p = self.plan
        # in the double-buffered stream every copy is a prefetch (started
        # one cell ahead of its consumer), so the plan's prefetch priority
        # applies to all of them — start and wait must describe the same
        # copy, so the priority rides the descriptor uniformly
        prio = p.prefetch_priority if p.residency == "strip_dma_db" else None
        return pallas_async_copy(
            self.x_ref.at[bi, pl.ds(src_row, rows), :,
                          pl.ds(ci * p.c_block, p.c_block)],
            self.buf.at[slot, pl.ds(slot_row, rows),
                        pl.ds(p.left, p.w_src), :],
            self.sem.at[slot],
            priority=prio,
        )

    def _dma(self, window, slot, start: bool):
        """Start (``start``) or wait the copy of ``window`` into ``slot``.

        A resident frame is one copy of the whole source.  A strip window
        is one copy of a shape fixed per strip class: each edge strip has
        its own, under ``pl.when``, and every interior strip shares one at
        a moving row offset.  Start and wait pick the same variant."""
        p = self.plan
        bi, ti, ci = window
        if not p.is_dma:
            dma = self._copy(0, p.h_src, slot, p.top, bi, ci)
            dma.start() if start else dma.wait()
            return
        edges, interior = p.strip_copies()
        for t, src_row, rows, slot_row in edges:
            @pl.when(ti == t)
            def _edge(src_row=src_row, rows=rows, slot_row=slot_row):
                if start:
                    self._zero(slot, 0, slot_row, 0, p.w_tot)
                    end = slot_row + rows
                    self._zero(slot, end, p.in_rows - end, 0, p.w_tot)
                    self._copy(src_row, rows, slot, slot_row, bi, ci).start()
                else:
                    self._copy(src_row, rows, slot, slot_row, bi, ci).wait()
        if interior is not None:
            first, last = interior

            @pl.when((ti >= first) & (ti <= last))
            def _inner():
                dma = self._copy(ti * p.tile_h * p.stride - p.top,
                                 p.in_rows, slot, 0, bi, ci)
                dma.start() if start else dma.wait()

    # -- the one public op ---------------------------------------------------

    def get(self) -> "StripWindow":
        """The current cell's staged window, ``(in_rows, w_tot, c_block)``.

        * a resident non-overlapping stream — its BlockSpec block,
        * resident — a view into the frame of this cell's (batch, channel
          block), copied whole the first time the pair comes up and
          prefetched into the other slot one pair ahead,
        * strip_dma — start + wait one async copy into slot 0,
        * strip_dma_db — wait the copy a previous cell prefetched (cell 0
          bootstraps its own), after starting the NEXT cell's prefetch so
          the strip stream stays one window ahead of compute.
        """
        p = self.plan
        if not p.staged:
            return StripWindow(self.x_ref, (0,), 0, p.tile_h, p.w_tot)

        step = self._step()
        here = self._window_here()

        @pl.when(step == 0)
        def _halo():
            self._zero_fixed_halo()

        if not p.is_dma:
            period = p.fetch_period
            fetch = step // period
            slot = fetch % 2

            @pl.when(step == 0)
            def _warmup():
                self._dma(here, 0, start=True)

            @pl.when(step % period == 0)
            def _switch():
                @pl.when(step + period < p.n_steps)
                def _prefetch():
                    self._dma(self._window_at(step + period), 1 - slot,
                              start=True)

                self._dma(here, slot, start=False)

            return StripWindow(self.buf, (slot,),
                               here[1] * p.tile_h * p.stride, p.in_rows,
                               p.w_tot)

        if p.residency == "strip_dma":
            self._dma(here, 0, start=True)
            self._dma(here, 0, start=False)
            return StripWindow(self.buf, (0,), 0, p.in_rows, p.w_tot)

        # strip_dma_db: the scratch slots revolve across grid cells — the
        # first cell warms the stream, every cell prefetches its successor.
        @pl.when(step == 0)
        def _warmup():
            self._dma(here, 0, start=True)

        @pl.when(step + 1 < p.n_steps)
        def _prefetch():
            self._dma(self._window_at(step + 1), (step + 1) % p.n_slots,
                      start=True)

        slot = step % p.n_slots
        self._dma(here, slot, start=False)
        return StripWindow(self.buf, (slot,), 0, p.in_rows, p.w_tot)


class StripWindow:
    """A staged window as a VMEM ref view: ``ref[lead..., row0 + r, c, :]``.

    Kernels read it through :meth:`read`, which loads straight from the
    ref.  A stride-2 conv tap is a STRIDED REF LOAD here — Mosaic cannot
    take a strided slice of a loaded value, but loads every s-th row and
    column of a VMEM ref directly.
    """

    def __init__(self, ref, lead: tuple, row0, rows: int, cols: int):
        self.ref, self.lead, self.row0 = ref, lead, row0
        self.rows, self.cols = rows, cols

    def read(self, row: int = 0, col: int = 0, rows: Optional[int] = None,
             cols: Optional[int] = None, stride: int = 1):
        """Rows ``row, row+stride, ...`` (``rows`` of them) by columns
        ``col, col+stride, ...`` (``cols`` of them), all channels; the
        defaults read the whole window."""
        return strided_read(self.ref, self.lead, self.row0 + row, col,
                            self.rows if rows is None else rows,
                            self.cols if cols is None else cols, stride)


def strided_read(ref, lead: tuple, row, col: int, rows: int, cols: int,
                 stride: int = 1):
    """``ref[*lead, row::stride][:rows], [col::stride][:cols], :]`` as one
    (strided when ``stride > 1``) VMEM load."""
    st = stride if stride > 1 else None
    return ref[(*lead, pl.ds(row, rows, stride=st),
                pl.ds(col, cols, stride=st), slice(None))]
