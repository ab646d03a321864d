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

* ``"resident"`` — the legacy rendering: the input is BlockSpec-blocked
  into VMEM (full padded height for halo'd streams, per-strip blocks for
  non-overlapping streams) and windows are ``pl.ds`` slices.  Cheapest
  when the whole (channel-block of the) input fits VMEM and the channel
  grid has one block; priced honestly by the ``resident`` traffic model.
* ``"strip_dma"`` — the input lives in the ``ANY``/HBM memory space; each
  grid cell issues one async copy of exactly its halo'd strip window into
  a single VMEM scratch slot and waits on it before computing.  HBM words
  = the strip-staging accounting (halo rows re-read, never re-written).
* ``"strip_dma_db"`` — same windows, **double-buffered**: two scratch
  slots + two DMA semaphores; each cell prefetches the *next* grid cell's
  window while computing its own, so the strip stream pipelines behind
  compute.  Identical HBM words to ``strip_dma`` (double-buffering buys
  overlap, not traffic) at 2x the strip scratch.

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

    * ``h_tot`` / ``w_tot`` — full (padded) rows / width of the source
      tensor, as launched: bounds for the last window's slice.  A window
      spans the whole launched width: Mosaic slices an HBM array only in
      whole (8, 128) tiles, and the launch pads the width to the tap
      reach rounded up to whole sublanes (``common.launch_geometry``).
    * ``c_block`` — channel lanes per window (a multiple of 128).
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

    def __post_init__(self):
        validate_residency(self.residency)
        assert len(self.window_dims) == 3 and all(
            0 <= d < len(self.grid) for d in self.window_dims), self

    @property
    def in_rows(self) -> int:
        """Rows per halo'd window (``tile_h`` when non-overlapping)."""
        return (self.tile_h - 1) * self.stride + self.k_h

    @property
    def is_dma(self) -> bool:
        return self.residency != "resident"

    @property
    def halo(self) -> bool:
        """Whether consecutive windows overlap (conv-style strips)."""
        return self.k_h > 1 or self.stride > 1

    @property
    def n_slots(self) -> int:
        return max(1, staging_slots(self.residency))

    @property
    def n_steps(self) -> int:
        return math.prod(self.grid)

    # -- launch-side helpers -------------------------------------------------

    def in_spec(self, index_map) -> pl.BlockSpec:
        """BlockSpec for the staged input.

        ``index_map`` maps grid indices to the RESIDENT block position
        (full-height channel block for halo'd streams, per-strip block for
        non-overlapping streams); DMA modes ignore it — the ref arrives
        un-blocked in the ANY space and the engine carves windows itself.
        """
        if self.is_dma:
            return pl.BlockSpec(memory_space=pl.ANY)
        rows = self.h_tot if self.halo else self.tile_h
        return pl.BlockSpec((1, rows, self.w_tot, self.c_block), index_map)

    def scratch_shapes(self, dtype) -> tuple:
        """Engine scratch to append to the kernel's ``scratch_shapes``:
        the slot buffer plus the per-slot DMA semaphore array.  Empty for
        ``resident``."""
        if not self.is_dma:
            return ()
        return (pltpu.VMEM((self.n_slots, self.in_rows, self.w_tot,
                            self.c_block), dtype),
                pltpu.SemaphoreType.DMA((self.n_slots,)))

    def take_scratch(self, scratch: tuple) -> tuple:
        """Split a kernel's trailing scratch refs: (engine_refs, rest)."""
        n = 2 if self.is_dma else 0
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
        prefetch_priority=prefetch_priority)
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
    plan's residency (slice, blocking DMA, or double-buffered DMA with
    next-window prefetch).
    """

    def __init__(self, plan: StripPlan, x_ref, stage_refs: tuple):
        self.plan = plan
        self.x_ref = x_ref
        if plan.is_dma:
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

    # -- DMA issue -----------------------------------------------------------

    def _dma(self, window, slot):
        p = self.plan
        bi, ti, ci = window
        row0 = ti * p.tile_h * p.stride
        # in the double-buffered stream every copy is a prefetch (started
        # one cell ahead of its consumer), so the plan's prefetch priority
        # applies to all of them — start and wait must describe the same
        # copy, so the priority rides the descriptor uniformly
        prio = p.prefetch_priority if p.residency == "strip_dma_db" else None
        return pallas_async_copy(
            self.x_ref.at[bi, pl.ds(row0, p.in_rows), :,
                          pl.ds(ci * p.c_block, p.c_block)],
            self.buf.at[slot],
            self.sem.at[slot],
            priority=prio,
        )

    # -- the one public op ---------------------------------------------------

    def get(self) -> "StripWindow":
        """The current cell's staged window, ``(in_rows, w_tot, c_block)``.

        * resident — a view into the VMEM-resident block,
        * strip_dma — start + wait one async copy into slot 0,
        * strip_dma_db — wait the copy a previous cell prefetched (cell 0
          bootstraps its own), after starting the NEXT cell's prefetch so
          the strip stream stays one window ahead of compute.
        """
        p = self.plan
        if not p.is_dma:
            if not p.halo:
                return StripWindow(self.x_ref, (0,), 0, p.tile_h, p.w_tot)
            _, ti, _ = self._window_here()
            return StripWindow(self.x_ref, (0,), ti * p.tile_h * p.stride,
                               p.in_rows, p.w_tot)

        step = self._step()
        here = self._window_here()
        if p.residency == "strip_dma":
            dma = self._dma(here, 0)
            dma.start()
            dma.wait()
            return StripWindow(self.buf, (0,), 0, p.in_rows, p.w_tot)

        # strip_dma_db: the scratch slots revolve across grid cells — the
        # first cell warms the stream, every cell prefetches its successor.
        @pl.when(step == 0)
        def _warmup():
            self._dma(here, 0).start()

        @pl.when(step + 1 < p.n_steps)
        def _prefetch():
            self._dma(self._window_at(step + 1),
                      (step + 1) % p.n_slots).start()

        slot = step % p.n_slots
        self._dma(here, slot).wait()
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
