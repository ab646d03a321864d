"""Analytical traffic / energy / latency model for the four dataflows
(Sec. V of the paper): WS baseline, IS baseline, WS ConvDK, IS ConvDK.

Accounting rules (each rule cites the paper sentence it encodes):

* **Traffic words** — 8-bit words crossing a buffer port.
  - IB side: ifmap words into the tile array (TRF for WS, TM for IS).
  - WB side: weight words into the tile array (TM for WS, TRF for IS).
  - OB side: ofmap words out of the accumulators.
* **Latency clocks** (Sec. IV-D):
  - TRF strip write = 1 clk per load event, tiles in parallel ("All TRFs are
    loaded ... at a single write cycle").
  - TM writes are word-by-word, 1 clk/word per tile; kernel duplication costs
    one extra clk per duplicated word ("9 cycles for the original weights and
    one additional cycle per duplicated weight" -> 2*k^2 for a duplicated 3x3).
  - OB write = 1 clk per 64-wide output round.
  - Compute = 10 clks per compute cycle (pipelined bit-serial 8-bit MAC);
    each compute cycle retires one output element per active tile.
  - DRAM traffic is pipelined behind compute (checked, flagged if it is not).
* **Energy** (Sec. V-C): DRAM 20 pJ/bit; IB/WB/OB SRAM access 1.139 pJ/bit;
  TM write 0.017 pJ/bit; TRF write 0.028 pJ/bit.  Physical TM/TRF bits
  written include duplicated copies; buffer-port energy counts unique words.

Interpretation choices (under-specified in the paper, fixed here and
documented in DESIGN.md):

1. WS-baseline TRF loads carry the k_h*k_w patch per output element with no
   inter-output reuse (the under-utilization the paper criticizes).
2. ConvDK strips exploit *vertical halo reuse*: consecutive output rows of
   the same (channel, strip) job share k_h - s input rows already resident
   in the register file, so only s*ia_len fresh words are fetched per new
   row.  This is the "maximizing data reuse" that yields the paper's
   77-87 % buffer-traffic reduction; without it the ceiling is 1 - s/k.
3. Tiles run asynchronously: total compute clocks = total sub-cycles /
   64-way parallelism, with kernel duplication across idle tiles providing
   the parallel slack (Sec. III-B "duplicated over idle tiles").
4. The headline "buffer traffic" metric (Fig. 7(c)) counts the IB- and
   WB-side streams; OB words are identical across dataflows and are
   reported separately (they enter energy and latency regardless).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .tiling import (
    DWLayer,
    MacroConfig,
    baseline_is_utilization,
    baseline_ws_utilization,
    plan_layer,
)

Dataflow = str  # "ws_base" | "is_base" | "ws_convdk" | "is_convdk"
DATAFLOWS: Tuple[Dataflow, ...] = ("ws_base", "is_base", "ws_convdk", "is_convdk")


@dataclass
class LayerCost:
    """All accounting for one layer under one dataflow."""

    layer: DWLayer
    dataflow: Dataflow
    # traffic words (8-bit) per buffer port
    ib_words: int = 0
    wb_words: int = 0
    ob_words: int = 0
    # physical bits written into tile storage (includes duplicate copies)
    tm_write_words: int = 0
    trf_write_words: int = 0
    # DRAM words (same for all dataflows; loop-nest and buffers fixed)
    dram_words: int = 0
    # latency, clocks
    ib_clks: int = 0
    wb_clks: int = 0
    ob_clks: int = 0
    compute_cycles: int = 0   # x10 clks each
    # utilization of the stationary memory (TM), 0..1
    tm_utilization: float = 0.0

    @property
    def buffer_words(self) -> int:
        """Fig. 7(c) metric: input-side buffer streams (see module note 4)."""
        return self.ib_words + self.wb_words

    @property
    def buffer_words_all(self) -> int:
        return self.ib_words + self.wb_words + self.ob_words

    @property
    def buffer_clks(self) -> int:
        return self.ib_clks + self.wb_clks + self.ob_clks

    @property
    def compute_clks(self) -> int:
        return self.compute_cycles * 10

    @property
    def total_clks(self) -> int:
        return self.buffer_clks + self.compute_clks

    def energy_pj(self, m: MacroConfig) -> Dict[str, float]:
        dram = self.dram_words * 8 * m.e_dram_pj
        buf = (self.ib_words + self.wb_words + self.ob_words) * 8 * m.e_buffer_pj
        tm = self.tm_write_words * 8 * m.e_tm_write_pj
        trf = self.trf_write_words * 8 * m.e_trf_write_pj
        return {"dram": dram, "buffer": buf, "tm": tm, "trf": trf,
                "total": dram + buf + tm + trf}

    def latency_ns(self, m: MacroConfig) -> float:
        return self.total_clks / m.clk_hz * 1e9

    def dram_pipelined_ok(self, m: MacroConfig) -> bool:
        """Sec. IV-D: DRAM transfer must hide behind compute."""
        dram_ns = self.dram_words / (m.dram_bw_gbps * 1e9) * 1e9
        return dram_ns <= self.compute_clks / m.clk_hz * 1e9


def _dram_words(layer: DWLayer) -> int:
    return layer.ifmap_words + layer.kernel_words + layer.ofmap_words


def _p64(x: int, m: MacroConfig) -> int:
    """Ceil-divide by the tile count (64-way spatial parallelism)."""
    return math.ceil(x / m.n_tiles)


# ---------------------------------------------------------------------------
# WS baseline — conventional weight-stationary CIM dataflow
# ---------------------------------------------------------------------------

def cost_ws_base(layer: DWLayer, m: MacroConfig = MacroConfig()) -> LayerCost:
    k2 = layer.k * layer.k
    outs = layer.out_h * layer.out_w
    ch_rounds = math.ceil(layer.c / m.n_tiles)

    ib_words = layer.c * outs * k2          # k^2 patch per output, no reuse
    wb_words = layer.c * k2                 # weights written once, stationary
    ob_words = layer.ofmap_words

    return LayerCost(
        layer=layer, dataflow="ws_base",
        ib_words=ib_words, wb_words=wb_words, ob_words=ob_words,
        tm_write_words=wb_words, trf_write_words=ib_words,
        dram_words=_dram_words(layer),
        ib_clks=ch_rounds * outs,           # 1-clk parallel TRF strip writes
        wb_clks=ch_rounds * k2,             # word-by-word TM writes
        ob_clks=_p64(layer.ofmap_words, m),
        compute_cycles=ch_rounds * outs,    # 1 output / tile / compute cycle
        tm_utilization=baseline_ws_utilization(layer),
    )


# ---------------------------------------------------------------------------
# IS baseline — input-stationary (Morphable-CIM-like)
# ---------------------------------------------------------------------------

def cost_is_base(layer: DWLayer, m: MacroConfig = MacroConfig()) -> LayerCost:
    k, s = layer.k, layer.s
    k2 = k * k
    outs = layer.out_h * layer.out_w
    ch_rounds = math.ceil(layer.c / m.n_tiles)

    # IS baseline (Morphable-CIM-like): the IA row strip is stationary in the
    # TM, re-written word-by-word per output row with no halo reuse (Sec. V-B
    # / VI: "the TMs are frequently re-written word-by-word"); the WEIGHTS
    # stream through the TRF per output element — Fig. 7(d): "in the IS
    # baseline, the weight movement is dominant".
    ib_words = layer.c * layer.out_h * k * layer.padded_w
    wb_words = layer.c * outs * k2          # weight patch per output
    ob_words = layer.ofmap_words

    return LayerCost(
        layer=layer, dataflow="is_base",
        ib_words=ib_words, wb_words=wb_words, ob_words=ob_words,
        tm_write_words=ib_words, trf_write_words=wb_words,
        dram_words=_dram_words(layer),
        ib_clks=_p64(ib_words, m),          # word-by-word TM writes
        wb_clks=ch_rounds * outs,           # 1-clk TRF weight events
        ob_clks=_p64(layer.ofmap_words, m),
        compute_cycles=ch_rounds * outs,
        tm_utilization=baseline_is_utilization(layer, m),
    )


# ---------------------------------------------------------------------------
# ConvDK dataflows (WS and IS variants share the BIG/LITTLE plan)
# ---------------------------------------------------------------------------

def _convdk_common(layer: DWLayer, m: MacroConfig):
    plan = plan_layer(layer, m)
    k, s = layer.k, layer.s
    # fresh ifmap words per (channel, strip) job over all output rows:
    # k_h rows for the first output row, s new rows for each of the rest
    # (vertical halo reuse inside the register file; module note 2).
    row_factor = k + (layer.out_h - 1) * s
    ia_words_per_ch = sum(sp.sched.ia_len for sp in plan.strips) * row_factor
    ifmap_stream_words = layer.c * ia_words_per_ch
    # one output element per sub-cycle; async tile packing (module note 3)
    total_subcycles = layer.c * layer.out_h * sum(
        sp.sched.out_len for sp in plan.strips
    )
    compute_cycles = _p64(total_subcycles, m)
    # strip-load events: one per (tile job, output row)
    load_events = plan.jobs * layer.out_h
    return plan, ifmap_stream_words, compute_cycles, load_events


def cost_ws_convdk(layer: DWLayer, m: MacroConfig = MacroConfig()) -> LayerCost:
    plan, ifmap_words, compute_cycles, load_events = _convdk_common(layer, m)
    k2 = layer.k * layer.k
    dup_blocks = sum(sp.sched.N for sp in plan.strips)

    wb_words = layer.c * k2                 # unique weights read from WB once
    # physical TM bits include the N duplicated copies (multi-access write)
    tm_write_words = layer.c * dup_blocks * k2

    return LayerCost(
        layer=layer, dataflow="ws_convdk",
        ib_words=ifmap_words, wb_words=wb_words, ob_words=layer.ofmap_words,
        tm_write_words=tm_write_words, trf_write_words=ifmap_words,
        dram_words=_dram_words(layer),
        ib_clks=_p64(load_events, m),       # 1-clk parallel TRF strip writes
        # duplicated kernel write: 2*k^2 clks per assignment round (Sec. IV-B)
        wb_clks=plan.rounds * 2 * k2,
        ob_clks=_p64(layer.ofmap_words, m),
        compute_cycles=compute_cycles,
        tm_utilization=plan.tm_utilization,
    )


def cost_is_convdk(layer: DWLayer, m: MacroConfig = MacroConfig()) -> LayerCost:
    plan, ifmap_words, compute_cycles, load_events = _convdk_common(layer, m)
    k2 = layer.k * layer.k
    dup_blocks = sum(sp.sched.N for sp in plan.strips)

    # IS: the IA strip is stationary in the TM (word-by-word writes, with the
    # same vertical halo reuse); the DUPLICATED kernel sits in the TRF and is
    # loaded once per (channel, strip) job, staying resident across all rows.
    wb_words = plan.jobs * k2               # unique kernel words per job
    trf_write_words = plan.jobs * dup_blocks * k2

    return LayerCost(
        layer=layer, dataflow="is_convdk",
        ib_words=ifmap_words, wb_words=wb_words, ob_words=layer.ofmap_words,
        tm_write_words=ifmap_words, trf_write_words=trf_write_words,
        dram_words=_dram_words(layer),
        ib_clks=_p64(ifmap_words, m),       # word-by-word TM writes
        wb_clks=_p64(plan.jobs, m),         # 1-clk TRF weight events
        ob_clks=_p64(layer.ofmap_words, m),
        compute_cycles=compute_cycles,
        tm_utilization=plan.tm_utilization,
    )


COST_FNS: Dict[Dataflow, Callable[..., LayerCost]] = {
    "ws_base": cost_ws_base,
    "is_base": cost_is_base,
    "ws_convdk": cost_ws_convdk,
    "is_convdk": cost_is_convdk,
}


# ---------------------------------------------------------------------------
# Network-level aggregation (Figs. 7-8)
# ---------------------------------------------------------------------------

@dataclass
class NetworkCost:
    name: str
    dataflow: Dataflow
    layers: List[LayerCost] = field(default_factory=list)

    def _sum(self, attr: str) -> int:
        return sum(getattr(c, attr) for c in self.layers)

    @property
    def buffer_words(self) -> int:
        return self._sum("buffer_words")

    @property
    def buffer_words_all(self) -> int:
        return self._sum("buffer_words_all")

    @property
    def dram_words(self) -> int:
        return self._sum("dram_words")

    @property
    def buffer_clks(self) -> int:
        return self._sum("buffer_clks")

    @property
    def compute_clks(self) -> int:
        return self._sum("compute_clks")

    @property
    def total_clks(self) -> int:
        return self._sum("total_clks")

    def energy_pj(self, m: MacroConfig = MacroConfig()) -> Dict[str, float]:
        tot: Dict[str, float] = {"dram": 0.0, "buffer": 0.0, "tm": 0.0,
                                 "trf": 0.0, "total": 0.0}
        for c in self.layers:
            for key, v in c.energy_pj(m).items():
                tot[key] += v
        return tot

    def mean_tm_utilization(self) -> float:
        """Compute-cycle-weighted mean TM utilization (Fig. 7(a))."""
        num = sum(c.tm_utilization * c.compute_cycles for c in self.layers)
        den = sum(c.compute_cycles for c in self.layers)
        return num / den if den else 0.0

    def latency_ms(self, m: MacroConfig = MacroConfig()) -> float:
        return self.total_clks / m.clk_hz * 1e3


def evaluate_network(
    name: str,
    layers: Iterable[DWLayer],
    dataflow: Dataflow,
    macro: MacroConfig = MacroConfig(),
) -> NetworkCost:
    fn = COST_FNS[dataflow]
    net = NetworkCost(name=name, dataflow=dataflow)
    for layer in layers:
        net.layers.append(fn(layer, macro))
    return net


def compare_networks(
    name: str, layers: Iterable[DWLayer], macro: MacroConfig = MacroConfig()
) -> Dict[Dataflow, NetworkCost]:
    layers = list(layers)
    return {df: evaluate_network(name, layers, df, macro) for df in DATAFLOWS}


def reduction(base: float, ours: float) -> float:
    """Percent reduction vs a baseline (positive = we are smaller)."""
    return 100.0 * (1.0 - ours / base) if base else 0.0


# ---------------------------------------------------------------------------
# TPU-kernel HBM traffic model (the executable analogue of the CIM model)
#
# The CIM accounting above prices IB/WB/OB buffer ports; the Pallas kernels
# pay the same structural costs at the HBM<->VMEM boundary.  These functions
# price the two executable separable-block pipelines so core/autotune.py can
# pick a fused schedule per layer shape (per-layer schedule selection a la
# MIREDO) and tests/benchmarks can assert fused < staged:
#
# * staged: stage_row_strips materializes overlapping strips (halo rows
#   written AND re-read), the DW output round-trips through HBM before the
#   pointwise matmul.
# * fused:  each strip is DMA'd once per c_out block straight from the
#   unstaged input; DW output stays in VMEM; only the block output is
#   written.
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Input residency: how the fused kernels stage their big input streams.
#
# ``resident``      — BlockSpec keeps the full padded height of a channel
#                     block in VMEM; strip windows are pl.ds slices.  Pallas
#                     refetches the whole block every time the block index
#                     changes, so with more than one channel block the input
#                     is re-read at FULL height per revisiting grid cell.
# ``strip_dma``     — input lives in ANY/HBM; each grid cell DMAs exactly
#                     its halo'd strip window into one VMEM scratch slot.
#                     HBM words = the strip-staging accounting (halo rows
#                     re-read across strips, never re-written).
# ``strip_dma_db``  — same windows, double-buffered (2 slots + prefetch of
#                     the next cell's window): identical HBM words, 2x the
#                     strip scratch, DMA latency hidden behind compute.
#
# The executable engine is ``kernels.staging``; these constants and the
# residency-aware pricing below keep the model and the kernels in lockstep.
# ---------------------------------------------------------------------------

RESIDENCY_MODES: Tuple[str, ...] = ("resident", "strip_dma", "strip_dma_db")
DEFAULT_RESIDENCY = "strip_dma_db"


def validate_residency(residency: str) -> str:
    if residency not in RESIDENCY_MODES:
        raise ValueError(
            f"residency must be one of {RESIDENCY_MODES}, got {residency!r}")
    return residency


def staging_slots(residency: str) -> int:
    """VMEM strip-scratch slots a residency mode allocates (0 = the input
    is BlockSpec-resident instead of engine-staged)."""
    validate_residency(residency)
    return {"resident": 0, "strip_dma": 1, "strip_dma_db": 2}[residency]


# TPU memory holds arrays in (sublane, lane) tiles: the last dim rounds up
# to 128 lanes and the second-to-last to 8 sublanes of 32-bit words (16
# for 2-byte, 32 for 1-byte dtypes).  Mosaic slices and DMAs only whole
# tiles, so the kernels launch every array padded to them.
LANES = 128
SUBLANES = 8
_SUBLANE_BYTES = SUBLANES * 4


def pick_channel_block(c: int, cap: int = 128) -> int:
    """Channel block size the Mosaic TPU compiler accepts.

    A block's lane (last) dim must be a multiple of 128 or the whole array
    dim, and a strip DMA out of HBM needs the array's channel dim itself
    to be a multiple of 128.  So the kernels pad channels to
    ``round_up(c, 128)`` and block them in 128-lane multiples: the widest
    multiple of 128 up to ``cap`` that divides the padded count.  The
    padding costs no HBM storage — XLA's tiled layout already stores a
    channel dim in whole 128-lane tiles — but it does cost the zero lanes'
    MACs.  ``cap`` must be a multiple of 128.
    """
    if cap % LANES:
        raise ValueError(f"cap must be a multiple of {LANES}, got {cap}")
    c_pad = _round_up(max(c, 1), LANES)
    return max(b for b in range(LANES, cap + 1, LANES) if c_pad % b == 0)


def launch_width(out_w: int, s: int, k: int, w_padded: int
                 ) -> Tuple[int, int]:
    """``(out_wk, w_tot)`` of a strip-tiled conv launch: the kernel's
    output width in whole sublanes (a ``(rows, out_wk, C)`` tile reshapes
    to a matrix only then), and the launched input width — the padded
    width or the reach of ``out_wk`` taps, whichever is wider — in whole
    sublanes (a strip DMA moves whole (8, 128) tiles)."""
    out_wk = _round_up(out_w, SUBLANES)
    return out_wk, _round_up(max(w_padded, (out_wk - 1) * s + k), SUBLANES)


def _launch_w(shape) -> int:
    """Input width of a shape's kernel launch (``launch_width``): what a
    staged window or a resident block holds in VMEM per row."""
    return launch_width(shape.out_w, shape.s, shape.k, shape.padded_w)[1]


def vmem_tile_bytes(dims: Tuple[int, ...], dtype_bytes: int = 4) -> int:
    """VMEM bytes one array of ``dims`` occupies, tile padding included."""
    *lead, sub, lane = (1,) * max(0, 2 - len(dims)) + tuple(dims)
    sublanes = _SUBLANE_BYTES // dtype_bytes
    return (math.prod(lead) * _round_up(sub, sublanes)
            * _round_up(lane, LANES) * dtype_bytes)


@dataclass(frozen=True)
class SeparableShape:
    """One depthwise-separable block instance as the TPU kernel sees it."""

    b: int          # batch
    h: int          # ifmap height (pre-padding)
    w: int          # ifmap width
    c_in: int       # depthwise / expanded channels
    c_out: int      # pointwise projection channels
    k: int          # square kernel
    s: int          # stride
    dtype_bytes: int = 4

    @property
    def out_h(self) -> int:
        return -(-self.h // self.s)

    @property
    def out_w(self) -> int:
        return -(-self.w // self.s)

    @property
    def padded_w(self) -> int:
        return (self.out_w - 1) * self.s + self.k

    @property
    def padded_h(self) -> int:
        return (self.out_h - 1) * self.s + self.k

    @classmethod
    def from_dw_layer(cls, layer: DWLayer, c_out: int, b: int = 1,
                      dtype_bytes: int = 4) -> "SeparableShape":
        return cls(b=b, h=layer.h, w=layer.w, c_in=layer.c, c_out=c_out,
                   k=layer.k, s=layer.s, dtype_bytes=dtype_bytes)


@dataclass(frozen=True)
class HBMTraffic:
    """HBM words moved by one block under one pipeline.

    ``dma_issues`` counts the explicit strip-window async copies the
    staging engine issues (0 for ``resident``, whose input moves through
    implicit BlockSpec fetches, and for the staged baselines) — the
    issue-rate side of the latency story the byte counts cannot show.
    """

    read_words: int
    write_words: int
    dtype_bytes: int = 4
    dma_issues: int = 0

    @property
    def total_words(self) -> int:
        return self.read_words + self.write_words

    @property
    def total_bytes(self) -> int:
        return self.total_words * self.dtype_bytes


def _strip_counts(shape: SeparableShape, tile_h: int) -> Tuple[int, int]:
    """(n_th, in_rows): row-strip count and staged rows per strip."""
    tile_h = max(1, min(tile_h, shape.out_h))
    n_th = -(-shape.out_h // tile_h)
    in_rows = (tile_h - 1) * shape.s + shape.k
    return n_th, in_rows


def _covered_rows(shape, tile_h: int) -> int:
    """Rows of the input as LAUNCHED: the kernels height-cover-pad so the
    last strip's window stays in bounds, so when ``tile_h`` does not
    divide ``out_h`` this exceeds ``padded_h`` — the resident BlockSpec
    keeps (and refetches) this full height, not just ``padded_h``."""
    tile_h = max(1, min(tile_h, shape.out_h))
    n_th = -(-shape.out_h // tile_h)
    return (n_th * tile_h - 1) * shape.s + shape.k


def staged_separable_traffic(
    shape: SeparableShape, tile_h: int, c_block: int = 128
) -> HBMTraffic:
    """HBM traffic of the staged two-kernel pipeline.

    1. stage_row_strips: read the padded input once, WRITE the overlapping
       strips tensor (halo rows duplicated in HBM),
    2. DW kernel: read the strips + DW taps, write the DW output,
    3. PW matmul: re-read the DW output + PW weight, write the block output.
    """
    n_th, in_rows = _strip_counts(shape, tile_h)
    strips = shape.b * n_th * in_rows * shape.padded_w * shape.c_in
    ifmap = shape.b * shape.padded_h * shape.padded_w * shape.c_in
    tile_h_eff = max(1, min(tile_h, shape.out_h))
    dw_out = shape.b * n_th * tile_h_eff * shape.out_w * shape.c_in
    out = shape.b * shape.out_h * shape.out_w * shape.c_out
    w_dw = shape.k * shape.k * shape.c_in
    w_pw = shape.c_in * shape.c_out
    reads = ifmap + strips + w_dw + dw_out + w_pw
    writes = strips + dw_out + out
    return HBMTraffic(reads, writes, shape.dtype_bytes)


def _n_co_blocks(c_out: int, c_block: int) -> int:
    return -(-c_out // min(c_block, max(8, _round_up(c_out, 8))))


def _n_chan_blocks(c: int, c_block: int) -> int:
    cb = pick_channel_block(c, c_block)
    return _round_up(c, cb) // cb


def fused_separable_traffic(
    shape: SeparableShape, tile_h: int, c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
) -> HBMTraffic:
    """HBM traffic of the fused single-pass pipeline under one residency.

    ``strip_dma`` / ``strip_dma_db``: each (strip, c_in block) window is
    DMA'd once per c_out block straight from the unstaged HBM input (halo
    rows re-read across strips but never written) — double-buffering moves
    the same words, earlier.  ``resident``: the full padded height of a
    channel block is BlockSpec-fetched, and REFETCHED whenever the block
    index changes — with more than one c_in block that is every grid cell,
    which is exactly the honest price of the legacy rendering.  In every
    mode the DW output lives and dies in VMEM, the only activation write
    is the block output, and weight blocks are re-fetched per revisiting
    grid cell.
    """
    validate_residency(residency)
    n_th, in_rows = _strip_counts(shape, tile_h)
    n_co = -(-shape.c_out // min(c_block, max(8, shape.c_out)))
    n_ci = _n_chan_blocks(shape.c_in, c_block)
    strips = shape.b * n_th * in_rows * shape.padded_w * shape.c_in
    # resident fetches move the input at its LAUNCHED height (height-cover
    # padding included), not just padded_h
    x_full = shape.b * _covered_rows(shape, tile_h) * shape.padded_w \
        * shape.c_in
    out = shape.b * shape.out_h * shape.out_w * shape.c_out
    w_dw = shape.k * shape.k * shape.c_in * n_th * n_co
    w_pw = shape.c_in * shape.c_out * n_th
    if residency == "resident":
        x_reads = x_full * (n_th * n_co if n_ci > 1 else 1)
        issues = 0
    else:
        x_reads = strips * n_co
        issues = shape.b * n_th * n_co * n_ci
    reads = x_reads + w_dw + w_pw
    writes = out
    return HBMTraffic(reads, writes, shape.dtype_bytes, issues)


def separable_staging_bytes(
    shape: SeparableShape, tile_h: int,
    residency: str = DEFAULT_RESIDENCY, c_block: int = 128,
) -> int:
    """VMEM bytes the fused separable kernel's INPUT stream occupies under
    one residency: the slot buffer(s) for the DMA modes (2x for
    double-buffering), the full-padded-height channel block otherwise."""
    validate_residency(residency)
    _n_th, in_rows = _strip_counts(shape, tile_h)
    ci = pick_channel_block(shape.c_in, c_block)
    if residency == "resident":
        # the launched (height-cover-padded) block, not just padded_h,
        # double-buffered by the BlockSpec pipeline
        return 2 * vmem_tile_bytes(
            (_covered_rows(shape, tile_h), _launch_w(shape), ci),
            shape.dtype_bytes)
    return staging_slots(residency) * vmem_tile_bytes(
        (in_rows, _launch_w(shape), ci), shape.dtype_bytes)


# ---------------------------------------------------------------------------
# MBConv (EfficientNet) two-pass traffic model
#
# The SE squeeze (global pool) between DW and PW breaks the single-strip
# residency of the fused separable pipeline: the projection cannot start
# until every strip's DW output has been pooled.  The two-pass fused
# schedule keeps the DW tensor out of the staged HBM round-trips anyway:
#
# * pass 1: expand-PW + DW per strip, the SE pool accumulated on-chip; the
#   DW output is either RETAINED (written once to HBM, re-read once by pass
#   2) or DISCARDED (pass 2 recomputes expand+DW from the input strips).
# * pass 2: the SE scale folds into the projection-PW contraction in the
#   same VMEM residency as the (retained or recomputed) DW block.
#
# The retain/recompute crossover is a pure traffic tradeoff: retain pays
# E * (1 + n_co) words for the DW tensor E; recompute pays the input strips
# and expand/DW weights again, n_co more times.  ``mbconv_fused_traffic``
# prices both so the autotuner can pick per layer shape.
# ---------------------------------------------------------------------------


MBCONV_MODES: Tuple[str, ...] = ("retain", "recompute")


@dataclass(frozen=True)
class MBConvShape:
    """One MBConv block instance as the TPU kernels see it."""

    b: int          # batch
    h: int          # ifmap height (pre-padding)
    w: int          # ifmap width
    c_in: int       # block input channels
    c_mid: int      # expanded channels (the DW / SE width)
    c_out: int      # projection output channels
    k: int          # square DW kernel
    s: int          # stride
    se_ratio: float = 0.25
    dtype_bytes: int = 4

    @property
    def out_h(self) -> int:
        return -(-self.h // self.s)

    @property
    def out_w(self) -> int:
        return -(-self.w // self.s)

    @property
    def padded_w(self) -> int:
        return (self.out_w - 1) * self.s + self.k

    @property
    def padded_h(self) -> int:
        return (self.out_h - 1) * self.s + self.k

    @property
    def has_se(self) -> bool:
        """``se_ratio <= 0`` means NO squeeze-excite at all (the V3 blocks
        that skip it, and every Fused-MBConv block): no pool, no MLP, no
        gate — the kernels skip those stages outright and the model must
        price zero words for them."""
        return self.se_ratio > 0

    @property
    def c_se(self) -> int:
        """SE bottleneck width — EfficientNet sizes it off the BLOCK INPUT
        channels, not the expanded width.  Zero when the block has no SE."""
        if not self.has_se:
            return 0
        return max(1, int(self.c_in * self.se_ratio))

    @property
    def has_expand(self) -> bool:
        return self.c_mid != self.c_in

    @property
    def se_words(self) -> int:
        """SE MLP parameter words (two FCs + biases); zero without SE."""
        if not self.has_se:
            return 0
        return 2 * self.c_mid * self.c_se + self.c_se + self.c_mid


def _mbconv_common(shape: MBConvShape, tile_h: int, c_block: int):
    n_th, in_rows = _strip_counts(
        SeparableShape(b=shape.b, h=shape.h, w=shape.w, c_in=shape.c_in,
                       c_out=shape.c_out, k=shape.k, s=shape.s), tile_h)
    tile_h_eff = max(1, min(tile_h, shape.out_h))
    cm_block = pick_channel_block(shape.c_mid, c_block)
    n_cm = _round_up(shape.c_mid, cm_block) // cm_block
    n_co = _n_co_blocks(shape.c_out, c_block)
    strips = shape.b * n_th * in_rows * shape.padded_w * shape.c_in
    # DW tensor words as retained on HBM (whole strips incl. masked rows)
    e_rows = shape.b * n_th * tile_h_eff * shape.out_w * shape.c_mid
    out = shape.b * shape.out_h * shape.out_w * shape.c_out
    w_exp = shape.c_in * shape.c_mid if shape.has_expand else 0
    w_dw = shape.k * shape.k * shape.c_mid
    w_proj = shape.c_mid * shape.c_out
    pool = shape.b * shape.c_mid
    return n_th, n_cm, n_co, strips, e_rows, out, w_exp, w_dw, w_proj, pool


def mbconv_pass_traffic(
    shape: MBConvShape, tile_h: int, mode: str = "retain",
    c_block: int = 128, residency: str = DEFAULT_RESIDENCY,
) -> Tuple[HBMTraffic, HBMTraffic]:
    """Per-pass HBM traffic of the two-pass fused MBConv pipeline.

    Returns ``(pass1, pass2)`` such that their fields SUM exactly to
    ``mbconv_fused_traffic`` (that function is defined as the merge, so
    the split cannot drift).  The boundary between the two passes is the
    SE-pool barrier:

    * ``pass1``: input strip reads per c_mid block + per-strip expand/DW
      weight refetches + the SE pool write, the SE MLP words (the MLP
      runs on the pass-1 pool before pass 2 can gate), and — under
      ``mode == "retain"`` — the one DW-tensor retain write.
    * ``pass2``: the retained-DW re-read per c_out block (or the
      recompute re-read of strips + expand/DW weights), the SE scale +
      projection-weight reads, and the block's only activation write.

    A no-SE block (``shape.has_se == False``) has no pool barrier: the
    kernels drop every pool/scale/MLP word, and under ``recompute`` pass
    1 is skipped ENTIRELY (it would produce nothing), so its pass-1
    figures here are exactly zero — the single remaining launch does all
    the work and is priced on pass 2.

    The split is what cross-block pipelining prices: pass 2 of block i
    and pass 1 of block i+1 touch disjoint buffers (pass 2 reads DW_i /
    scale_i and writes act_{i+1}; pass 1 of i+1 reads act_{i+1} strips as
    they land and writes DW_{i+1} / pool_{i+1}), so a boundary can pay
    ``max`` instead of ``sum`` — see ``boundary_overlap_us``.
    """
    if mode not in MBCONV_MODES:
        raise ValueError(mode)
    validate_residency(residency)
    (n_th, n_cm, n_co, strips, e_rows, out, w_exp, w_dw, w_proj,
     pool) = _mbconv_common(shape, tile_h, c_block)
    n_ci = _n_chan_blocks(shape.c_in, c_block)
    # launched height incl. height-cover padding (see _covered_rows)
    x_full = shape.b * _covered_rows(shape, tile_h) * shape.padded_w \
        * shape.c_in
    resident = residency == "resident"
    se = shape.has_se
    scale = pool if se else 0                      # SE gate, (B, C_mid) words
    # pass 1: strips per c_mid block + per-strip weight refetches + pool.
    # se=off + recompute: the kernel skips pass 1 outright — zero words.
    issues1 = 0
    reads1 = 0
    writes1 = 0
    if se or mode == "retain":
        if resident:
            reads1 = x_full * (n_cm * n_th if n_ci > 1 else 1)
        else:
            reads1 = strips * n_cm
            issues1 += shape.b * n_cm * n_th * n_ci
        reads1 += (w_exp + w_dw) * n_th
    if se:
        writes1 += pool
        # SE MLP between passes (host-side; tiny but accounted with pass 1
        # — it consumes the pass-1 pool and must finish before pass 2 gates)
        reads1 += pool + shape.se_words
        writes1 += scale
    # pass 2
    issues2 = 0
    if mode == "retain":
        writes1 += e_rows                          # pass-1 DW retain write
        reads2 = e_rows * n_co + scale * n_th * n_co + w_proj * n_th
        if not resident:
            issues2 += shape.b * n_co * n_th * n_cm
    else:
        if resident:
            reads2 = x_full * (n_co * n_th * n_cm if n_ci > 1 else 1)
        else:
            reads2 = strips * n_cm * n_co
            issues2 += shape.b * n_co * n_th * n_cm * n_ci
        reads2 += ((w_exp + w_dw) * n_th * n_co
                   + scale * n_th * n_co + w_proj * n_th)
    writes2 = out
    return (HBMTraffic(reads1, writes1, shape.dtype_bytes, issues1),
            HBMTraffic(reads2, writes2, shape.dtype_bytes, issues2))


def mbconv_fused_traffic(
    shape: MBConvShape, tile_h: int, mode: str = "retain",
    c_block: int = 128, residency: str = DEFAULT_RESIDENCY,
) -> HBMTraffic:
    """HBM traffic of the two-pass fused MBConv pipeline (one mode, one
    residency).

    Pass 1 reads each input strip once per c_mid block (expand reduction
    innermost) and writes only the on-chip-accumulated SE pool — plus the
    DW tensor once when ``mode == "retain"``.  Pass 2 reads the retained DW
    tensor once per c_out block, or (``mode == "recompute"``) re-reads the
    input strips and expand/DW weights instead; either way the SE scale and
    projection happen in the same VMEM residency, and the only activation
    write of the whole block is the final output.

    Residency changes how the INPUT streams price: the DMA modes move
    exactly the halo'd strip windows (``strip_dma_db`` double-buffers the
    same words); ``resident`` BlockSpec-refetches the full padded height of
    a c_in block every revisiting grid cell.  The retained-DW re-read is a
    non-overlapping block stream, so its words are residency-invariant.

    Defined as the SUM of ``mbconv_pass_traffic`` — the whole-block total
    and the per-pass split cannot diverge.
    """
    p1, p2 = mbconv_pass_traffic(shape, tile_h, mode, c_block, residency)
    return HBMTraffic(p1.read_words + p2.read_words,
                      p1.write_words + p2.write_words,
                      shape.dtype_bytes, p1.dma_issues + p2.dma_issues)


def mbconv_staging_bytes(
    shape: MBConvShape, tile_h: int, mode: str = "retain",
    residency: str = DEFAULT_RESIDENCY, c_block: int = 128,
) -> int:
    """VMEM bytes the two-pass MBConv kernels' staged input streams occupy
    under one residency: the halo'd input-window stream (both passes'
    launches stage it identically) plus, for ``mode == "retain"``, the
    retained-DW block stream pass 2 re-reads."""
    validate_residency(residency)
    if mode not in MBCONV_MODES:
        raise ValueError(mode)
    tile_h_eff = max(1, min(tile_h, shape.out_h))
    in_rows = (tile_h_eff - 1) * shape.s + shape.k
    ci = pick_channel_block(shape.c_in, c_block)
    cm = pick_channel_block(shape.c_mid, c_block)
    slots = staging_slots(residency)
    dw_stream = vmem_tile_bytes((tile_h_eff, shape.out_w, cm),
                                shape.dtype_bytes)
    if residency == "resident":
        # the launched (height-cover-padded) block, not just padded_h; the
        # BlockSpec pipeline double-buffers both resident streams
        x_bytes = 2 * vmem_tile_bytes(
            (_covered_rows(shape, tile_h), _launch_w(shape), ci),
            shape.dtype_bytes)
        dw_bytes = 2 * dw_stream                  # per-strip resident block
    else:
        x_bytes = slots * vmem_tile_bytes((in_rows, _launch_w(shape), ci),
                                          shape.dtype_bytes)
        dw_bytes = slots * dw_stream
    return x_bytes + (dw_bytes if mode == "retain" else 0)


def mbconv_best_fused_traffic(
    shape: MBConvShape, tile_h: int, c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
) -> Tuple[str, HBMTraffic]:
    """(mode, traffic) of the cheaper two-pass variant at this (tile_h,
    residency)."""
    priced = [(m, mbconv_fused_traffic(shape, tile_h, m, c_block, residency))
              for m in MBCONV_MODES]
    return min(priced, key=lambda mt: mt[1].total_bytes)


def mbconv_staged_traffic(
    shape: MBConvShape, tile_h: int, c_block: int = 128
) -> HBMTraffic:
    """HBM traffic of the staged MBConv pipeline (the PR-1-era baseline):

    1. expand PW: read x + w_exp, write the expanded map,
    2. stage_row_strips over the expanded map (halo rows duplicated in HBM),
    3. DW kernel: read strips + taps, write the DW output,
    4. SE (when the block has one): read the DW output for the pool, run
       the MLP, then re-read AND re-write the DW output applying the gate,
    5. projection PW: re-read the (scaled) DW output + w_proj, write out.

    Exactly the weight-stationary-baseline behaviour the paper criticizes:
    the squeeze forces the whole DW tensor through HBM four more times.
    A no-SE block skips stage 4 entirely — the staged baseline saves its
    gate round-trips too, so the fused-vs-staged margin stays honest.
    """
    (n_th, _n_cm, _n_co, _strips, e_rows, out, w_exp, w_dw, w_proj,
     pool) = _mbconv_common(shape, tile_h, c_block)
    x_words = shape.b * shape.h * shape.w * shape.c_in
    xe = shape.b * shape.h * shape.w * shape.c_mid
    xe_pad = shape.b * shape.padded_h * shape.padded_w * shape.c_mid
    n_th_, in_rows = _strip_counts(
        SeparableShape(b=shape.b, h=shape.h, w=shape.w, c_in=shape.c_mid,
                       c_out=shape.c_out, k=shape.k, s=shape.s), tile_h)
    strips_e = shape.b * n_th_ * in_rows * shape.padded_w * shape.c_mid
    reads = (x_words + w_exp                      # expand
             + xe_pad                             # staging read
             + strips_e + w_dw                    # DW kernel
             + e_rows + w_proj)                   # projection read
    writes = ((xe if shape.has_expand else 0)     # expanded map
              + strips_e                          # staged strips
              + e_rows                            # DW output
              + out)
    if shape.has_se:
        reads += (e_rows + shape.se_words         # SE pool + MLP params
                  + e_rows + pool)                # gate apply read
        writes += (pool                           # gate
                   + e_rows)                      # scaled DW output
    if not shape.has_expand:
        reads -= x_words                          # no expand stage: DW stages
    return HBMTraffic(reads, writes, shape.dtype_bytes)


# ---------------------------------------------------------------------------
# Fused-MBConv (EfficientNet-V2) single-pass traffic model
#
# Fused-MBConv collapses the expand-PW + DW pair into ONE dense k x k
# convolution (C_in -> C_mid) and never carries SE, so nothing forces a
# pool barrier: the whole block — dense conv, activation, 1x1 projection —
# runs as a SINGLE pass in one VMEM residency.  The family reuses the
# MBConvShape vocabulary (c_mid is the dense conv's output width) with
# ``se_ratio == 0`` REQUIRED; its weights differ though: one dense
# k*k*c_in*c_mid tensor instead of expand + DW taps.
#
# Pass-split convention: the family is priced through the same
# ``(pass1, pass2)`` interface as MBConv so the network solver and the
# pipelining model stay family-generic — pass 1 carries the ENTIRE block
# and pass 2 is EXACTLY zero (property-tested).  A zero pass 2 is what
# keeps ``boundary_overlap_us`` honest at a single-pass producer: there
# is no pass-2 compute for the next block's pass-1 DMA to hide behind, so
# the boundary prices serial automatically (min(p2, p1) == 0).
# ---------------------------------------------------------------------------


def _require_no_se(shape: MBConvShape) -> None:
    if shape.has_se:
        raise ValueError(
            f"Fused-MBConv never carries SE; got se_ratio="
            f"{shape.se_ratio!r} — build the shape with se_ratio=0")


def fusedmb_pass_traffic(
    shape: MBConvShape, tile_h: int, c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
) -> Tuple[HBMTraffic, HBMTraffic]:
    """Per-pass HBM traffic of the single-pass Fused-MBConv pipeline:
    ``(whole_block, exactly_zero)``.

    The one launch reads each input strip once per (c_mid, c_out) block
    pair (the dense-conv c_in reduction is innermost, the projection's
    c_mid reduction next), refetches the dense conv weight per revisiting
    (strip, c_out) cell and the projection weight per strip, and writes
    only the block output — the expanded map lives and dies in VMEM,
    exactly the separable fusion story at MBConv widths.
    """
    _require_no_se(shape)
    validate_residency(residency)
    (n_th, n_cm, n_co, strips, _e_rows, out, _w_exp, _w_dw, w_proj,
     _pool) = _mbconv_common(shape, tile_h, c_block)
    n_ci = _n_chan_blocks(shape.c_in, c_block)
    w_conv = shape.k * shape.k * shape.c_in * shape.c_mid
    # launched height incl. height-cover padding (see _covered_rows)
    x_full = shape.b * _covered_rows(shape, tile_h) * shape.padded_w \
        * shape.c_in
    issues = 0
    if residency == "resident":
        reads = x_full * (n_co * n_th * n_cm if n_ci > 1 else 1)
    else:
        reads = strips * n_cm * n_co
        issues += shape.b * n_co * n_th * n_cm * n_ci
    reads += w_conv * n_th * n_co + w_proj * n_th
    return (HBMTraffic(reads, out, shape.dtype_bytes, issues),
            HBMTraffic(0, 0, shape.dtype_bytes, 0))


def fusedmb_fused_traffic(
    shape: MBConvShape, tile_h: int, c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
) -> HBMTraffic:
    """HBM traffic of the single-pass Fused-MBConv pipeline.  Defined as
    the sum of ``fusedmb_pass_traffic`` (whose pass 2 is exactly zero) —
    the whole-block total and the per-pass split cannot diverge."""
    p1, p2 = fusedmb_pass_traffic(shape, tile_h, c_block, residency)
    return HBMTraffic(p1.read_words + p2.read_words,
                      p1.write_words + p2.write_words,
                      shape.dtype_bytes, p1.dma_issues + p2.dma_issues)


def fusedmb_staged_traffic(
    shape: MBConvShape, tile_h: int, c_block: int = 128
) -> HBMTraffic:
    """HBM traffic of the staged Fused-MBConv pipeline (what
    ``convdk_fusedmb_staged`` actually runs):

    1. dense conv: read the input + w_conv, write the expanded map,
    2. projection PW: re-read the expanded map + w_proj, write out.

    The expanded map (c_mid = expand * c_in wide) makes the HBM
    round-trip the fusion deletes — the same story as the separable
    baseline, at Fused-MBConv widths."""
    _require_no_se(shape)
    del tile_h, c_block
    x_words = shape.b * shape.h * shape.w * shape.c_in
    xe = shape.b * shape.out_h * shape.out_w * shape.c_mid
    out = shape.b * shape.out_h * shape.out_w * shape.c_out
    w_conv = shape.k * shape.k * shape.c_in * shape.c_mid
    w_proj = shape.c_mid * shape.c_out
    reads = x_words + w_conv + xe + w_proj
    writes = xe + out
    return HBMTraffic(reads, writes, shape.dtype_bytes)


def fusedmb_staging_bytes(
    shape: MBConvShape, tile_h: int,
    residency: str = DEFAULT_RESIDENCY, c_block: int = 128,
) -> int:
    """VMEM bytes the Fused-MBConv kernel's INPUT stream occupies under
    one residency (single pass, no retained stream — the input window is
    the only staged tensor)."""
    _require_no_se(shape)
    validate_residency(residency)
    tile_h_eff = max(1, min(tile_h, shape.out_h))
    in_rows = (tile_h_eff - 1) * shape.s + shape.k
    ci = pick_channel_block(shape.c_in, c_block)
    if residency == "resident":
        # the launched (height-cover-padded) block, double-buffered
        return 2 * vmem_tile_bytes(
            (_covered_rows(shape, tile_h), _launch_w(shape), ci),
            shape.dtype_bytes)
    return staging_slots(residency) * vmem_tile_bytes(
        (in_rows, _launch_w(shape), ci), shape.dtype_bytes)


# ---------------------------------------------------------------------------
# Sharded traffic: per-device HBM + collective bytes
#
# ``kernels.convdk_sharded`` partitions the fused pipelines over the
# ("data", "model") mesh (an optional "pod" axis folds into the data
# factor as a pure data-parallel outer multiplier): batch on "data" for
# both families, c_out on "model" for separable (collective-free: the
# c_in reduction is local) and c_mid on "model" for MBConv (the SE
# squeeze FC and the projection PW reduce over the full expanded width,
# so each becomes a cross-device reduction).  The paper's reduction claim
# must be re-proved under this partitioning — Eyeriss-style reuse
# analysis does not transfer for free — so the model prices BOTH terms:
#
# * per-device HBM traffic = the single-device model evaluated at the
#   shard shape (batch/dp, channel grid/mp), and
# * collective words, per the schedule's **collective** axis:
#   - ``ring_allreduce``: 2*(mp-1) words per reduced word per model group
#     (reduce-scatter + all-gather; the result lands replicated), and
#   - ``psum_scatter`` (MBConv projection only): (mp-1) words per reduced
#     word — the reduce-scatter half alone, the pass-2 output leaving the
#     kernel SHARDED on c_out for a consumer that wants it that way.  The
#     SE squeeze partial always rings: the excite FC needs it replicated.
#   Words are summed over the dp model groups.  Non-divisible axes drop
#   to 1 (the ``spec_for`` policy).
#
# ``ShardedTraffic`` is the SINGLE source of truth for mesh-wide byte
# totals: ``core.autotune`` schedules carry these objects and delegate
# every total to them, so the solver and the model cannot diverge.
# ---------------------------------------------------------------------------


COLLECTIVE_MODES: Tuple[str, ...] = ("ring_allreduce", "psum_scatter")
DEFAULT_COLLECTIVE = "ring_allreduce"

# Inter-block layout axis: how a block's activation tensor sits across the
# "model" groups at a block BOUNDARY.  ``replicated`` is the classic form
# (every device holds the full (B_local, H, W, C) slice of its data group);
# ``model_sharded`` splits the channel dim over "model" — the form a
# psum_scatter pass-2 leaves behind, and the form an identity-expand MBConv
# (or sharded-c_in separable) entry can consume collective-free.
LAYOUT_MODES: Tuple[str, ...] = ("replicated", "model_sharded")
DEFAULT_LAYOUT = "replicated"


def validate_collective(collective: str) -> str:
    if collective not in COLLECTIVE_MODES:
        raise ValueError(
            f"collective must be one of {COLLECTIVE_MODES}, "
            f"got {collective!r}")
    return collective


def validate_layout(layout: str) -> str:
    if layout not in LAYOUT_MODES:
        raise ValueError(
            f"layout must be one of {LAYOUT_MODES}, got {layout!r}")
    return layout


@dataclass(frozen=True)
class ShardedTraffic:
    """One fused block under one (data, model) partitioning."""

    device: HBMTraffic           # HBM traffic of ONE device's shard
    collective_words: int        # interconnect words, summed over the mesh
    n_devices: int
    mesh_shape: Tuple[int, int] = (1, 1)
    collective: str = DEFAULT_COLLECTIVE   # reduction layout priced above
    in_layout: str = DEFAULT_LAYOUT        # how the input arrives
    transition_words: int = 0    # entry-side layout repay (all-gather words)

    @property
    def dtype_bytes(self) -> int:
        return self.device.dtype_bytes

    @property
    def per_device_bytes(self) -> int:
        return self.device.total_bytes

    @property
    def collective_bytes(self) -> int:
        return self.collective_words * self.dtype_bytes

    @property
    def transition_bytes(self) -> int:
        return self.transition_words * self.dtype_bytes

    @property
    def out_layout(self) -> str:
        """Layout the block's output LEAVES in: sharded on c_out after a
        psum_scatter pass-2, replicated otherwise."""
        _dp, mp = self.mesh_shape
        if mp > 1 and self.collective == "psum_scatter":
            return "model_sharded"
        return DEFAULT_LAYOUT

    @property
    def total_bytes(self) -> int:
        """All bytes moved anywhere: every device's HBM traffic plus the
        interconnect words (reductions AND any entry-side layout repay) —
        the number the staged single-device baseline is compared against."""
        return (self.device.total_bytes * self.n_devices
                + self.collective_bytes + self.transition_bytes)


def shard_factors(batch: int, channels: int,
                  mesh_shape: Tuple[int, int]) -> Tuple[int, int]:
    """Effective (data, model) split, matching ``kernels.can_shard_fused``
    exactly: the kernel routing is ALL-OR-NOTHING (a grid either runs the
    sharded wrapper on the whole mesh or falls back to one device), so if
    either mesh axis fails to divide its grid axis the whole layer prices
    as (1, 1) — the model must never describe a partitioning the kernels
    will not run."""
    dp, mp = mesh_shape
    if dp < 1 or mp < 1 or batch % dp != 0 or channels % mp != 0:
        return 1, 1
    return dp, mp


def separable_shard(
    shape: SeparableShape, mesh_shape: Tuple[int, int],
    in_layout: str = DEFAULT_LAYOUT,
) -> Tuple[SeparableShape, Tuple[int, int]]:
    """(per-device shard shape, effective factors) for the separable
    partitioning.

    ``replicated`` input: batch over "data", c_out over "model" (the PW
    reduction stays device-local).  ``model_sharded`` input: batch over
    "data", c_in over "model" — each device sees its channel slice of the
    input, DW is channel-local, and the PW contraction becomes a partial
    over the local c_in rows (collective priced separately)."""
    validate_layout(in_layout)
    if in_layout == "model_sharded":
        dp, mp = shard_factors(shape.b, shape.c_in, mesh_shape)
        if mp > 1:
            return replace(shape, b=shape.b // dp,
                           c_in=shape.c_in // mp), (dp, mp)
        return replace(shape, b=shape.b // dp), (dp, mp)
    dp, mp = shard_factors(shape.b, shape.c_out, mesh_shape)
    return replace(shape, b=shape.b // dp, c_out=shape.c_out // mp), (dp, mp)


def can_shard_input(shape: MBConvShape,
                    mesh_shape: Tuple[int, int]) -> bool:
    """True iff the MBConv block can CONSUME a c_in-sharded input without
    any entry collective: only the identity-expand form (c_mid == c_in)
    qualifies — its "expand" is elementwise, so device d's c_in slice is
    exactly the c_mid slice its DW taps need.  A real expand (e > 1) is a
    dense contraction over ALL of c_in, so every device needs the full
    input and a sharded arrival must be gathered back (priced as
    ``transition_words``, never a win — see ``sharded_mbconv_traffic``)."""
    _dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    return mp > 1 and not shape.has_expand


def mbconv_shard(
    shape: MBConvShape, mesh_shape: Tuple[int, int],
    in_layout: str = DEFAULT_LAYOUT,
) -> Tuple[MBConvShape, Tuple[int, int]]:
    """(per-device shard shape, effective factors) for the MBConv
    partitioning: batch over "data", c_mid over "model".  With a
    ``model_sharded`` input layout on an identity-expand block the input
    channels shard too (c_in == c_mid there), shrinking every pass-1
    strip read by the model factor."""
    validate_layout(in_layout)
    dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    local = replace(shape, b=shape.b // dp, c_mid=shape.c_mid // mp)
    if (in_layout == "model_sharded" and mp > 1 and not shape.has_expand):
        local = replace(local, c_in=shape.c_in // mp)
    return local, (dp, mp)


def _separable_collective_words(shape: SeparableShape, dp: int, mp: int,
                                collective: str) -> int:
    """Interconnect words of the sharded-c_in separable form: the PW
    contraction is a partial over each device's c_in rows, reduced across
    the model group — full ring under ``ring_allreduce`` (output lands
    replicated) or the reduce-scatter half under ``psum_scatter`` (output
    leaves sharded on c_out, zero-padded to the model factor)."""
    validate_collective(collective)
    if mp <= 1:
        return 0
    b_local = shape.b // dp
    out = b_local * shape.out_h * shape.out_w * shape.c_out
    if collective == "psum_scatter":
        return dp * (mp - 1) * (b_local * shape.out_h * shape.out_w
                                * scatter_c_out(shape.c_out, mp))
    return dp * 2 * (mp - 1) * out


def sharded_separable_traffic(
    shape: SeparableShape, tile_h: int, mesh_shape: Tuple[int, int] = (1, 1),
    c_block: int = 128, residency: str = DEFAULT_RESIDENCY,
    in_layout: str = DEFAULT_LAYOUT, collective: str = DEFAULT_COLLECTIVE,
) -> ShardedTraffic:
    """Per-device traffic of the sharded fused separable block.

    ``replicated`` input (default): batch on "data", c_out on "model";
    c_in stays replicated so the PW reduction is device-local and the
    collective term is zero.  ``model_sharded`` input: c_in shards on
    "model" instead — each device reads only its channel slice of the
    input (mp-fold fewer strip words) but the PW partial must reduce
    across the group, priced per ``collective``.  ``residency`` prices
    each device's input staging either way."""
    validate_layout(in_layout)
    local, (dp, mp) = separable_shard(shape, mesh_shape, in_layout)
    if in_layout == "model_sharded" and mp > 1:
        return ShardedTraffic(
            device=fused_separable_traffic(local, tile_h, c_block, residency),
            collective_words=_separable_collective_words(
                shape, dp, mp, collective),
            n_devices=dp * mp, mesh_shape=(dp, mp), collective=collective,
            in_layout=in_layout)
    return ShardedTraffic(
        device=fused_separable_traffic(local, tile_h, c_block, residency),
        collective_words=0, n_devices=dp * mp, mesh_shape=(dp, mp))


def sharded_separable_staged_traffic(
    shape: SeparableShape, tile_h: int, mesh_shape: Tuple[int, int] = (1, 1),
    c_block: int = 128,
) -> ShardedTraffic:
    """The staged two-kernel pipeline under the SAME partitioning — the
    baseline a sharded deployment would actually run (its PW reduction is
    also c_in-local, so it is collective-free too)."""
    local, (dp, mp) = separable_shard(shape, mesh_shape)
    return ShardedTraffic(
        device=staged_separable_traffic(local, tile_h, c_block),
        collective_words=0, n_devices=dp * mp, mesh_shape=(dp, mp))


def can_psum_scatter(shape: MBConvShape,
                     mesh_shape: Tuple[int, int]) -> bool:
    """True iff the psum_scatter pass-2 variant is runnable at this
    partitioning: the layer actually shards on "model".  Non-dividing
    c_out no longer rejects — the kernel zero-pads the projection columns
    to the next multiple of the model factor and scatters the padded dim
    (priced as such: see ``scatter_c_out``)."""
    _dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    return mp > 1


def scatter_c_out(c_out: int, mp: int) -> int:
    """Channel width a psum_scatter pass-2 actually moves: c_out rounded
    up to the model factor (the pad-to-mp columns are zeros of the padded
    projection weight, scattered like any other — wire words are honest
    about them)."""
    if mp <= 1:
        return c_out
    return _round_up(c_out, mp)


def layout_transition_words(
    b: int, h: int, w: int, c: int, mesh_shape: Tuple[int, int],
    producer_layout: str, consumer_layout: str,
) -> int:
    """Interconnect words to move a (b, h, w, c) activation from the
    producer's boundary layout to the consumer's: an all-gather of the
    missing (mp-1)/mp channel slices per model group (summed over the dp
    groups) when a sharded output feeds a replicated entry; free when the
    layouts match, and free when a replicated output feeds a sharded
    entry (each device slices locally)."""
    validate_layout(producer_layout)
    validate_layout(consumer_layout)
    dp, mp = mesh_shape
    if (mp <= 1 or producer_layout != "model_sharded"
            or consumer_layout == "model_sharded"):
        return 0
    b_local = b // dp if dp > 1 and b % dp == 0 else b
    # (mp-1) words per gathered word per model group — same convention as
    # the reduce-scatter half, so scatter + repay-gather == ring exactly
    return dp * (mp - 1) * b_local * h * w * scatter_c_out(c, mp)


def _mbconv_entry_transition_words(shape: MBConvShape, dp: int, mp: int,
                                   in_layout: str) -> int:
    """Entry-side repay when a c_in-sharded input feeds a REAL expand
    (e > 1): the dense expand contraction needs all of c_in on every
    device, so the entry all-gathers the missing slices — (mp-1) words
    per held word per model group, summed over the dp groups.  Zero for
    the identity-expand entry (the shard IS what the block needs) and for
    replicated arrivals."""
    if mp <= 1 or in_layout != "model_sharded" or not shape.has_expand:
        return 0
    b_local = shape.b // dp
    return dp * (mp - 1) * b_local * shape.h * shape.w * shape.c_in


def _mbconv_collective_words(shape: MBConvShape, dp: int, mp: int,
                             collective: str = DEFAULT_COLLECTIVE) -> int:
    """Interconnect words for the two c_mid reductions, per ``collective``:

    * the (B_local, C_se) SE squeeze partial always ring-all-reduces
      (2*(mp-1) words per reduced word per model group — the excite FC
      consumes it replicated);
    * the (B_local, H', W', C_out) projection partial ring-all-reduces
      under ``ring_allreduce`` or pays only the reduce-scatter half,
      (mp-1) words per reduced word, under ``psum_scatter`` — the pass-2
      output then leaves the kernel sharded on c_out.  Non-dividing c_out
      scatters at the zero-padded width (``scatter_c_out``)."""
    squeeze, proj = _mbconv_collective_split(shape, dp, mp, collective)
    return squeeze + proj


def _mbconv_collective_split(
    shape: MBConvShape, dp: int, mp: int,
    collective: str = DEFAULT_COLLECTIVE,
) -> Tuple[int, int]:
    """``_mbconv_collective_words`` split by pass: ``(squeeze, proj)``
    mesh-wide words.  The SE-squeeze ring belongs to pass 1 (pass 2
    cannot gate until it lands); the projection reduction belongs to
    pass 2.  ``_mbconv_collective_words`` is defined as the sum."""
    validate_collective(collective)
    if mp <= 1:
        return 0, 0
    b_local = shape.b // dp
    # c_se is 0 for a no-SE block, so the squeeze ring vanishes exactly
    # when the kernel emits no squeeze psum
    squeeze = b_local * shape.c_se
    proj = b_local * shape.out_h * shape.out_w * shape.c_out
    if collective == "psum_scatter":
        proj_words = (mp - 1) * (b_local * shape.out_h * shape.out_w
                                 * scatter_c_out(shape.c_out, mp))
    else:
        proj_words = 2 * (mp - 1) * proj
    return dp * 2 * (mp - 1) * squeeze, dp * proj_words


def sharded_mbconv_traffic(
    shape: MBConvShape, tile_h: int, mode: str = "retain",
    mesh_shape: Tuple[int, int] = (1, 1), c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
    collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> ShardedTraffic:
    """Per-device traffic + collective bytes of the sharded two-pass
    MBConv.

    Batch splits over "data", c_mid over "model".  Two reductions cross
    the model groups: the (B_local, C_se) SE squeeze partial (the pass-1
    pool leaving the chip once, before the pass-2 gate) and the
    (B_local, H', W', C_out) projection partial — the latter priced per
    ``collective`` (``ring_allreduce`` replicates the output,
    ``psum_scatter`` halves the wire words and leaves it sharded on
    c_out).  ``residency`` prices each device's input staging.

    ``in_layout`` prices the ENTRY: an identity-expand block consumes a
    ``model_sharded`` input collective-free at mp-fold smaller strip
    reads (c_in shards with c_mid); a real expand must gather a sharded
    arrival back (``transition_words``) — the honest reason e > 1
    boundaries never win by staying sharded."""
    validate_layout(in_layout)
    local, (dp, mp) = mbconv_shard(shape, mesh_shape, in_layout)
    eff_layout = in_layout if mp > 1 else DEFAULT_LAYOUT
    return ShardedTraffic(
        device=mbconv_fused_traffic(local, tile_h, mode, c_block, residency),
        collective_words=_mbconv_collective_words(shape, dp, mp, collective),
        n_devices=dp * mp, mesh_shape=(dp, mp), collective=collective,
        in_layout=eff_layout,
        transition_words=_mbconv_entry_transition_words(
            shape, dp, mp, eff_layout))


def sharded_mbconv_staged_traffic(
    shape: MBConvShape, tile_h: int, mesh_shape: Tuple[int, int] = (1, 1),
    c_block: int = 128, collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> ShardedTraffic:
    """The staged MBConv pipeline under the SAME partitioning.

    With c_mid sharded, the staged path pays the IDENTICAL collectives
    (its SE squeeze and projection also reduce over the full expanded
    width, and its projection could equally reduce-scatter) — priced
    under the SAME ``collective`` mode as the fused pipeline, so the
    fused-vs-staged margin under sharding is decided by the HBM side,
    exactly the paper's claim re-proved per partition.  ``in_layout``
    prices its entry identically too."""
    validate_layout(in_layout)
    local, (dp, mp) = mbconv_shard(shape, mesh_shape, in_layout)
    eff_layout = in_layout if mp > 1 else DEFAULT_LAYOUT
    return ShardedTraffic(
        device=mbconv_staged_traffic(local, tile_h, c_block),
        collective_words=_mbconv_collective_words(shape, dp, mp, collective),
        n_devices=dp * mp, mesh_shape=(dp, mp), collective=collective,
        in_layout=eff_layout,
        transition_words=_mbconv_entry_transition_words(
            shape, dp, mp, eff_layout))


def fusedmb_shard(
    shape: MBConvShape, mesh_shape: Tuple[int, int],
) -> Tuple[MBConvShape, Tuple[int, int]]:
    """(per-device shard shape, effective factors) for the Fused-MBConv
    partitioning: batch over "data", c_mid over "model".  c_in NEVER
    shards — the dense k x k conv contracts over all of it on every
    device, so the input must arrive replicated (the kernel rejects
    anything else)."""
    _require_no_se(shape)
    dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    return replace(shape, b=shape.b // dp, c_mid=shape.c_mid // mp), (dp, mp)


def _fusedmb_collective_words(shape: MBConvShape, dp: int, mp: int,
                              collective: str) -> int:
    """Interconnect words of the sharded Fused-MBConv: ONE reduction — the
    (B_local, H', W', C_out) projection partial over the c_mid shards —
    priced per ``collective`` exactly like the MBConv projection.  No SE
    means no squeeze ring: the projection collective is the family's
    entire wire bill."""
    validate_collective(collective)
    if mp <= 1:
        return 0
    b_local = shape.b // dp
    if collective == "psum_scatter":
        return dp * (mp - 1) * (b_local * shape.out_h * shape.out_w
                                * scatter_c_out(shape.c_out, mp))
    out = b_local * shape.out_h * shape.out_w * shape.c_out
    return dp * 2 * (mp - 1) * out


def sharded_fusedmb_traffic(
    shape: MBConvShape, tile_h: int, mesh_shape: Tuple[int, int] = (1, 1),
    c_block: int = 128, residency: str = DEFAULT_RESIDENCY,
    collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> ShardedTraffic:
    """Per-device traffic + collective bytes of the sharded single-pass
    Fused-MBConv: batch on "data", c_mid on "model", projection partial
    reduced per ``collective``.

    ``in_layout`` must be ``replicated`` — mirroring the kernel, which
    raises for a sharded arrival (the dense conv needs all of c_in).  A
    sharded producer feeding this family repays its layout at the
    BOUNDARY (``layout_transition_words``), never inside the block."""
    validate_layout(in_layout)
    if in_layout != DEFAULT_LAYOUT:
        raise ValueError(
            f"fusedmb consumes replicated arrivals only, got {in_layout!r}")
    local, (dp, mp) = fusedmb_shard(shape, mesh_shape)
    return ShardedTraffic(
        device=fusedmb_fused_traffic(local, tile_h, c_block, residency),
        collective_words=_fusedmb_collective_words(shape, dp, mp, collective),
        n_devices=dp * mp, mesh_shape=(dp, mp), collective=collective,
        in_layout=DEFAULT_LAYOUT)


def sharded_fusedmb_staged_traffic(
    shape: MBConvShape, tile_h: int, mesh_shape: Tuple[int, int] = (1, 1),
    c_block: int = 128, collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> ShardedTraffic:
    """The staged Fused-MBConv pipeline under the SAME partitioning — its
    projection also reduces over the c_mid shards, so it pays the
    identical collective and the fused-vs-staged margin is decided by the
    HBM side, per partition."""
    validate_layout(in_layout)
    if in_layout != DEFAULT_LAYOUT:
        raise ValueError(
            f"fusedmb consumes replicated arrivals only, got {in_layout!r}")
    local, (dp, mp) = fusedmb_shard(shape, mesh_shape)
    return ShardedTraffic(
        device=fusedmb_staged_traffic(local, tile_h, c_block),
        collective_words=_fusedmb_collective_words(shape, dp, mp, collective),
        n_devices=dp * mp, mesh_shape=(dp, mp), collective=collective,
        in_layout=DEFAULT_LAYOUT)


# ---------------------------------------------------------------------------
# Cross-block pipelining: per-pass costs + overlap-aware latency
#
# Pass 2 of block i and pass 1 of block i+1 touch disjoint buffers (pass 2
# reads DW_i / scale_i and writes act_{i+1}; pass 1 of i+1 reads act_{i+1}
# strips as they land and writes DW_{i+1} / pool_{i+1}), so a block-chain
# executor can hide the consumer's pass-1 DMA behind the producer's pass-2
# compute — the staging engine's double-buffering generalized one level
# up.  A pipelined boundary then prices as max(pass2_us, pass1_us) instead
# of their sum.  The verdict is calibrated, not asserted: the pass
# latencies come from the fitted ``PerfCoefficients`` applied to the
# per-pass traffic split above.
# ---------------------------------------------------------------------------


OVERLAP_MODES: Tuple[str, ...] = ("serial", "pipelined")
DEFAULT_OVERLAP = "serial"


def validate_overlap(overlap: str) -> str:
    if overlap not in OVERLAP_MODES:
        raise ValueError(
            f"overlap must be one of {OVERLAP_MODES}, got {overlap!r}")
    return overlap


@dataclass(frozen=True)
class MBConvPassCosts:
    """The two-pass split of one sharded MBConv block's costs: per-device
    HBM traffic plus the mesh-wide collective words each pass must wait
    on.  Sums exactly to ``sharded_mbconv_traffic`` (property-tested)."""

    pass1: HBMTraffic            # one device's pass-1 (+SE MLP) traffic
    pass2: HBMTraffic            # one device's pass-2 traffic
    pass1_collective_words: int  # SE squeeze ring + any entry repay
    pass2_collective_words: int  # projection reduction (ring or scatter)

    @property
    def dtype_bytes(self) -> int:
        return self.pass1.dtype_bytes

    @property
    def pass1_collective_bytes(self) -> int:
        return self.pass1_collective_words * self.dtype_bytes

    @property
    def pass2_collective_bytes(self) -> int:
        return self.pass2_collective_words * self.dtype_bytes


def sharded_mbconv_pass_costs(
    shape: MBConvShape, tile_h: int, mode: str = "retain",
    mesh_shape: Tuple[int, int] = (1, 1), c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
    collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> MBConvPassCosts:
    """Per-pass split of ``sharded_mbconv_traffic`` at the same point.

    Device traffic splits via ``mbconv_pass_traffic`` on the shard shape;
    collective words split via ``_mbconv_collective_split`` (squeeze →
    pass 1, projection → pass 2).  Any entry-side layout repay gathers
    BEFORE the first strip can stream, so it lands on pass 1 — one more
    reason a boundary with transition words never pipelines.
    """
    validate_layout(in_layout)
    local, (dp, mp) = mbconv_shard(shape, mesh_shape, in_layout)
    eff_layout = in_layout if mp > 1 else DEFAULT_LAYOUT
    p1, p2 = mbconv_pass_traffic(local, tile_h, mode, c_block, residency)
    squeeze, proj = _mbconv_collective_split(shape, dp, mp, collective)
    entry = _mbconv_entry_transition_words(shape, dp, mp, eff_layout)
    return MBConvPassCosts(pass1=p1, pass2=p2,
                           pass1_collective_words=squeeze + entry,
                           pass2_collective_words=proj)


def sharded_fusedmb_pass_costs(
    shape: MBConvShape, tile_h: int,
    mesh_shape: Tuple[int, int] = (1, 1), c_block: int = 128,
    residency: str = DEFAULT_RESIDENCY,
    collective: str = DEFAULT_COLLECTIVE,
    in_layout: str = DEFAULT_LAYOUT,
) -> MBConvPassCosts:
    """Per-pass split of ``sharded_fusedmb_traffic`` at the same point:
    the whole single-pass block (HBM AND the projection collective) lands
    on pass 1, pass 2 is exactly zero.  ``boundary_overlap_us`` then
    prices a boundary BEHIND this block as serial automatically — a
    single-pass producer has no pass-2 compute for the next block's
    pass-1 DMA to hide behind, and the model must never pretend it does.
    """
    validate_layout(in_layout)
    if in_layout != DEFAULT_LAYOUT:
        raise ValueError(
            f"fusedmb consumes replicated arrivals only, got {in_layout!r}")
    local, (dp, mp) = fusedmb_shard(shape, mesh_shape)
    p1, p2 = fusedmb_pass_traffic(local, tile_h, c_block, residency)
    proj = _fusedmb_collective_words(shape, dp, mp, collective)
    return MBConvPassCosts(pass1=p1, pass2=p2,
                           pass1_collective_words=proj,
                           pass2_collective_words=0)


# ---------------------------------------------------------------------------
# Measured calibration: fitting walltime coefficients onto the byte model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PerfCoefficients:
    """Least-squares fit of measured walltime onto the modeled cost terms.

    ``walltime_us ~ base_us + us_per_mb * bytes/1e6
                  + us_per_dma_issue * dma_issues
                  + us_per_collective_mb * collective_bytes/1e6``

    The two non-byte terms are exactly the costs the byte model cannot
    see: the per-issue overhead of explicit strip DMA (the open question
    behind ``resident`` winning half the B0 table) and the latency of a
    collective word relative to an HBM word.  ``rms_us`` is the fit
    residual — report it next to the coefficients, a fit that explains
    nothing should not decide knobs.
    """

    base_us: float
    us_per_mb: float
    us_per_dma_issue: float
    us_per_collective_mb: float
    n_samples: int
    rms_us: float

    def as_dict(self) -> Dict[str, float]:
        return {
            "base_us": self.base_us,
            "us_per_mb": self.us_per_mb,
            "us_per_dma_issue": self.us_per_dma_issue,
            "us_per_collective_mb": self.us_per_collective_mb,
            "n_samples": self.n_samples,
            "rms_us": self.rms_us,
        }


def fit_perf_coefficients(samples: Iterable[dict]) -> PerfCoefficients:
    """Fit :class:`PerfCoefficients` from measured samples.

    Each sample is a dict with ``walltime_us`` and ``modeled_bytes``
    (required) plus optional ``dma_issues`` and ``collective_bytes``.
    Cost columns that are constant across the sample set are dropped
    from the regression (their coefficient is reported as 0.0 — the
    data cannot identify them), so a single-device CPU sweep with no
    collectives still yields a well-posed byte/issue fit.
    """
    import numpy as np

    rows = [(float(s["walltime_us"]), float(s["modeled_bytes"]) / 1e6,
             float(s.get("dma_issues", 0)),
             float(s.get("collective_bytes", 0)) / 1e6)
            for s in samples]
    if not rows:
        raise ValueError("fit_perf_coefficients needs at least one sample")
    y = np.array([r[0] for r in rows])
    cols = {"us_per_mb": np.array([r[1] for r in rows]),
            "us_per_dma_issue": np.array([r[2] for r in rows]),
            "us_per_collective_mb": np.array([r[3] for r in rows])}
    active = [k for k, v in cols.items() if float(v.max() - v.min()) > 0]
    design = np.column_stack(
        [np.ones(len(rows))] + [cols[k] for k in active])
    if len(rows) < design.shape[1]:
        raise ValueError(
            f"fit needs >= {design.shape[1]} samples for "
            f"{design.shape[1]} free terms, got {len(rows)}")
    coef, *_ = np.linalg.lstsq(design, y, rcond=None)
    fitted = dict.fromkeys(cols, 0.0)
    for name, value in zip(active, coef[1:]):
        fitted[name] = float(value)
    rms = float(np.sqrt(np.mean((design @ coef - y) ** 2)))
    return PerfCoefficients(
        base_us=float(coef[0]), n_samples=len(rows), rms_us=rms, **fitted)


def predict_walltime_us(coeffs: PerfCoefficients, *, modeled_bytes: float,
                        dma_issues: float = 0,
                        collective_bytes: float = 0) -> float:
    """Walltime the calibrated model expects for one cost point."""
    return (coeffs.base_us
            + coeffs.us_per_mb * modeled_bytes / 1e6
            + coeffs.us_per_dma_issue * dma_issues
            + coeffs.us_per_collective_mb * collective_bytes / 1e6)


# Fallback calibration for latency-shaped decisions (the overlap axis)
# when no fresh fit is installed: fit_perf_coefficients over the B0
# ``kernel_bench --measure --measure-scale 8 --measure-iters 1`` candidate
# sweep on this repo's CPU interpret-mode reference host (2026-08-09).
# CPU interpret walltimes swing under load (see ROADMAP PR-7 edges), so
# these decide only RELATIVE pass weights; deployments should install a
# host-local fit via ``set_perf_coefficients(fit_perf_coefficients(...))``
# — ``roofline_bench --bench`` prints one from any BENCH artifact.
DEFAULT_PERF_COEFFICIENTS = PerfCoefficients(
    base_us=-1508.24, us_per_mb=3559.22, us_per_dma_issue=68.68,
    us_per_collective_mb=0.0, n_samples=32, rms_us=4446.75)

_installed_coefficients: Optional[PerfCoefficients] = None


def set_perf_coefficients(coeffs: Optional[PerfCoefficients]) -> None:
    """Install a measured fit as the process-wide calibration (``None``
    reverts to ``DEFAULT_PERF_COEFFICIENTS``)."""
    global _installed_coefficients
    _installed_coefficients = coeffs


def get_perf_coefficients() -> PerfCoefficients:
    """The calibration latency-shaped decisions use: the installed fit
    if ``set_perf_coefficients`` provided one, else the defaults."""
    return (_installed_coefficients if _installed_coefficients is not None
            else DEFAULT_PERF_COEFFICIENTS)


def mbconv_pass_us(coeffs: PerfCoefficients, traffic: HBMTraffic,
                   collective_words: int = 0) -> float:
    """Calibrated walltime of ONE pass, floored at zero (an lstsq fit can
    go negative at tiny extrapolated points; a pass never takes negative
    time, and the floor keeps ``boundary_overlap_us`` monotone)."""
    return max(0.0, predict_walltime_us(
        coeffs, modeled_bytes=traffic.total_bytes,
        dma_issues=traffic.dma_issues,
        collective_bytes=collective_words * traffic.dtype_bytes))


def boundary_overlap_us(pass2_us: float, pass1_us: float,
                        overlap: str = DEFAULT_OVERLAP) -> float:
    """Modeled latency of one block boundary: the producer's pass-2 tail
    plus the consumer's pass-1 head when serialized, their ``max`` when
    the boundary pipelines (the consumer's pass-1 DMA streams behind the
    producer's pass-2 compute).  Both terms are >= 0, so pipelined <=
    serialized ALWAYS — the saving is ``min(pass2_us, pass1_us)``."""
    validate_overlap(overlap)
    if overlap == "pipelined":
        return max(pass2_us, pass1_us)
    return pass2_us + pass1_us


def overlap_saving_us(pass2_us: float, pass1_us: float) -> float:
    """Latency a pipelined boundary hides vs serialized: min of the two
    overlapped terms (sum - max)."""
    return min(pass2_us, pass1_us)
