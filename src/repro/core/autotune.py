"""Per-layer schedule selection for the fused ConvDK kernels.

MIREDO-style per-layer solving: instead of one fixed ``tile_h`` for every
block, each layer shape gets its own fused schedule, chosen by the
analytical HBM traffic model in ``core.perfmodel`` (primary) with an
optional measured fallback sweep (ground truth when the model cannot
separate candidates, or when a deployment wants real timings).  Two block
families are solved:

* separable (``FusedSchedule``): DW + PW in one pass — pick ``tile_h`` AND
  the input **residency** ("resident" | "strip_dma" | "strip_dma_db", the
  staging-engine axis: VMEM feasibility counts the slot buffers — 2x strip
  scratch for double-buffering — and the traffic model prices each mode);
* MBConv (``MBConvSchedule``): expand + DW + SE + PW in two passes — pick
  ``tile_h``, the residency, the pass-2 ``mode`` ("retain" writes the
  DW tensor to HBM once and re-reads it; "recompute" re-runs expand+DW
  from the input strips; the traffic model prices the crossover per layer
  shape), AND — under a model-sharded mesh — the ``collective`` axis
  ("ring_allreduce" | "psum_scatter": how the pass-2 projection partial
  is reduced across the model groups; scatter halves the wire words and
  leaves the output sharded on c_out).

Every schedule carries the ``perfmodel.ShardedTraffic`` pair it was
solved from and DELEGATES all byte totals to it (``_ScheduleTraffic``):
the solver optimizes exactly the bytes the model prices — there is no
second accounting to drift.

Schedule solving is trace-time work and must never re-run inside a jitted
step, so selections are cached.  The cache has two layers:

1. an in-process dict (always on), and
2. an optional JSON file under a configurable cache directory, keyed by
   (kernel kind, layer shape, dtype bytes, jax backend) — measured sweeps
   and model picks survive restarts and can ship as a lookup table.
   Enable it with ``set_schedule_cache_dir(path)`` or the
   ``CONVDK_CACHE_DIR`` environment variable; entries recorded from a
   measured sweep (``source == "measured"``) take priority over model
   picks for the same key.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, replace
from functools import lru_cache, wraps
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

from .perfmodel import (
    COLLECTIVE_MODES,
    DEFAULT_COLLECTIVE,
    DEFAULT_LAYOUT,
    DEFAULT_OVERLAP,
    DEFAULT_RESIDENCY,
    MBCONV_MODES,
    RESIDENCY_MODES,
    HBMTraffic,
    MBConvPassCosts,
    MBConvShape,
    PerfCoefficients,
    SeparableShape,
    ShardedTraffic,
    boundary_overlap_us,
    can_psum_scatter,
    can_shard_input,
    fusedmb_shard,
    fusedmb_staging_bytes,
    get_perf_coefficients,
    launch_width,
    layout_transition_words,
    mbconv_pass_us,
    mbconv_shard,
    mbconv_staging_bytes,
    pick_channel_block,
    separable_shard,
    separable_staging_bytes,
    shard_factors,
    sharded_fusedmb_pass_costs,
    sharded_fusedmb_staged_traffic,
    sharded_fusedmb_traffic,
    sharded_mbconv_pass_costs,
    sharded_mbconv_staged_traffic,
    sharded_mbconv_traffic,
    sharded_separable_staged_traffic,
    sharded_separable_traffic,
    validate_collective,
    validate_layout,
    validate_overlap,
    validate_residency,
    vmem_tile_bytes,
)
from . import telemetry
from .telemetry import measure

MeshShape = Tuple[int, int]   # ("data", "model") axis sizes, (1, 1) = 1 core

# Block activation vocabulary (mirrored by ``configs.base.ACT_MODES`` —
# configs sits above models and cannot be imported from core).  The act
# axis never changes a byte count, but it IS a schedule-cache key segment:
# entries must record the block variant they were solved for, so a future
# act-sensitive refinement (e.g. hard_swish's clip chain changing the
# VMEM scratch) can split the entries without orphaning them.
ACT_MODES: Tuple[str, ...] = ("silu", "relu", "hard_swish")
DEFAULT_ACT = "silu"

# Families a network CHAIN element may take (separable blocks are solved
# per-layer via ``get_fused_schedule`` and never enter the chain DP)
CHAIN_FAMILIES: Tuple[str, ...] = ("mbconv", "fusedmb")


def _plan_span(fn):
    """Times every call of a public solver entry, cache hit or miss, as
    the span ``autotune.plan`` (nested entries count once)."""
    @wraps(fn)
    def timed(*args, **kwargs):
        with telemetry.span("autotune.plan"):
            return fn(*args, **kwargs)
    return timed


def validate_act(act: str) -> str:
    if act not in ACT_MODES:
        raise ValueError(f"act must be one of {ACT_MODES}, got {act!r}")
    return act

# Solver preference among byte-identical residencies: double-buffering hides
# the strip DMA behind compute at 2x scratch, single-slot DMA is the
# VMEM-tight fallback, and full-height residency is the last resort (its
# traffic collapses only for single-channel-block layers that fit VMEM).
_RESIDENCY_RANK = {"strip_dma_db": 0, "strip_dma": 1, "resident": 2}


@dataclass(frozen=True)
class TPUConfig:
    """Budget knobs for fused-schedule selection on one core."""

    vmem_bytes: int = 16 * 1024 * 1024   # per-core VMEM budget
    c_block: int = 128                   # lane width
    tile_h_candidates: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)


class VMEMInfeasibleError(ValueError):
    """No candidate schedule of a layer fits the VMEM budget.  The solver
    raises it instead of handing the kernels a schedule Mosaic would
    refuse (or, worse, a scoped-VMEM overflow at launch)."""


def _infeasible(family: str, shape, tpu: "TPUConfig", **pins):
    pinned = "".join(f", {k}={v}" for k, v in pins.items() if v is not None)
    return VMEMInfeasibleError(
        f"no {family} schedule fits {tpu.vmem_bytes} B of VMEM for "
        f"{shape}{pinned}")


class _ScheduleTraffic:
    """Accounting VIEW shared by both schedule families.

    A schedule carries the two ``perfmodel.ShardedTraffic`` objects it was
    solved from — ``sharded`` (the fused pipeline) and ``staged`` (the
    identically partitioned staged baseline) — and every byte total here
    DELEGATES to them.  ``perfmodel`` is the single pricing authority for
    device bytes, collective bytes and DMA issues; the solver never
    re-derives a mesh-wide total, so the bytes the autotuner optimizes
    are — identically, not approximately — the bytes the traffic model
    prices (the anti-divergence property in tests/test_perfmodel_bands.py
    pins this down).  For the default ``mesh_shape == (1, 1)`` the device
    traffic is the whole layer (the PR-1 semantics, unchanged).  The
    staged baseline pays the SAME collective words (its reductions over
    the sharded channel axis are the same collectives, priced under the
    same ``collective`` mode), so the fused-vs-staged margin stays an
    HBM-side comparison."""

    @property
    def traffic(self) -> HBMTraffic:
        """PER-DEVICE fused HBM traffic (one shard of the launch)."""
        return self.sharded.device

    @property
    def staged_traffic(self) -> HBMTraffic:
        """PER-DEVICE staged-baseline HBM traffic."""
        return self.staged.device

    @property
    def mesh_shape(self) -> Tuple[int, int]:
        return self.sharded.mesh_shape

    @property
    def n_devices(self) -> int:
        return self.sharded.n_devices

    @property
    def collective(self) -> str:
        """The reduction layout the collectives were priced under."""
        return self.sharded.collective

    @property
    def collective_words(self) -> int:
        return self.sharded.collective_words

    @property
    def collective_bytes(self) -> int:
        return self.sharded.collective_bytes

    @property
    def in_layout(self) -> str:
        """Input layout the schedule was priced for (layout axis)."""
        return self.sharded.in_layout

    @property
    def out_layout(self) -> str:
        """Layout the block's output leaves in (sharded on c_out after a
        psum_scatter pass-2, replicated otherwise)."""
        return self.sharded.out_layout

    @property
    def transition_words(self) -> int:
        return self.sharded.transition_words

    @property
    def transition_bytes(self) -> int:
        """Entry-side layout repay (the all-gather a real-expand block
        pays to consume a c_in-sharded arrival)."""
        return self.sharded.transition_bytes

    @property
    def total_bytes(self) -> int:
        """All bytes moved anywhere (every device's HBM + collectives) —
        ``perfmodel.ShardedTraffic.total_bytes``, verbatim."""
        return self.sharded.total_bytes

    @property
    def staged_total_bytes(self) -> int:
        return self.staged.total_bytes

    @property
    def modeled_saving(self) -> float:
        """Fraction of staged bytes the fused schedule avoids."""
        base = self.staged.total_bytes
        return 1.0 - self.sharded.total_bytes / base if base else 0.0


@dataclass(frozen=True)
class FusedSchedule(_ScheduleTraffic):
    """One selected schedule for ``convdk_fused_separable``.

    The separable partitioning (c_out on "model") is collective-free, so
    its ``ShardedTraffic`` always has 0 collective words — the accounting
    view exists for symmetry with ``MBConvSchedule`` (doc on
    ``_ScheduleTraffic``)."""

    tile_h: int
    ci_block: int
    co_block: int
    sharded: ShardedTraffic      # fused pricing (the solver's objective)
    staged: ShardedTraffic       # identically partitioned staged baseline
    residency: str = DEFAULT_RESIDENCY   # input-staging mode


@dataclass(frozen=True)
class MBConvSchedule(_ScheduleTraffic):
    """One selected two-pass schedule for ``convdk_mbconv_fused``.

    Under a mesh the c_mid partitioning pays two cross-device reductions
    (SE squeeze + projection partials) priced inside ``sharded`` /
    ``staged`` under the schedule's **collective** axis — ring all-reduce
    or the psum_scatter pass-2 variant whose output leaves the kernel
    sharded on c_out (doc on ``_ScheduleTraffic``; ``self.collective``
    reads the solved mode)."""

    tile_h: int
    mode: str                    # "retain" | "recompute"
    ci_block: int
    cm_block: int
    co_block: int
    sharded: ShardedTraffic      # fused pricing (the solver's objective)
    staged: ShardedTraffic       # identically partitioned staged baseline
    residency: str = DEFAULT_RESIDENCY   # input-staging mode
    # entry-overlap the schedule was solved under: "pipelined" means this
    # block's pass 1 streams behind the upstream block's pass 2, so its
    # pass-1 footprint was feasibility-checked against HALF the VMEM
    # budget (the two co-resident stages split the core) — a genuinely
    # different solve, hence a cache-key axis (``ov=`` segment)
    overlap: str = DEFAULT_OVERLAP


@dataclass(frozen=True)
class FusedMBSchedule(_ScheduleTraffic):
    """One selected single-pass schedule for ``convdk_fusedmb_fused``.

    Fused-MBConv has no pass-2 mode axis (the whole block is one pass —
    its pass-2 figures are exactly zero, see
    ``perfmodel.fusedmb_pass_traffic``) and no layout axis (the dense
    conv needs all of c_in, so the entry is always replicated).  It keeps
    the residency, collective and overlap axes: the projection partial
    still reduces over the c_mid shards, and the block's single pass can
    still stream behind an upstream two-pass producer's pass 2 (the
    converse never holds — there is no pass 2 here to hide anything
    behind)."""

    tile_h: int
    ci_block: int
    cm_block: int
    co_block: int
    sharded: ShardedTraffic      # fused pricing (the solver's objective)
    staged: ShardedTraffic       # identically partitioned staged baseline
    residency: str = DEFAULT_RESIDENCY   # input-staging mode
    overlap: str = DEFAULT_OVERLAP       # entry overlap (see MBConvSchedule)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blocks(c: int, cap: int) -> int:
    return min(cap, _round_up(c, 8))


# ---------------------------------------------------------------------------
# persistent schedule cache
# ---------------------------------------------------------------------------

_CACHE_DIR_ENV = "CONVDK_CACHE_DIR"
_CACHE_FILE = "convdk_schedules.json"


def _backend() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:  # pragma: no cover - jax always importable here
        return "unknown"


class ScheduleCache:
    """Two-layer schedule cache: in-process dict + optional JSON file.

    Disk entries store only the *decision* (tile_h, mode, source); traffic
    numbers are deterministic functions of the shape and are rebuilt by the
    model on load, so the file format survives model refinements.
    """

    def __init__(self, directory: Optional[Path]):
        self.directory = Path(directory).expanduser() if directory else None
        self._mem: Dict[str, dict] = {}
        self._disk: Optional[Dict[str, dict]] = None   # lazily loaded

    @property
    def path(self) -> Optional[Path]:
        return self.directory / _CACHE_FILE if self.directory else None

    @staticmethod
    def _migrate_key(key: str) -> str:
        """Upgrade legacy cache keys in place, chaining the six schema
        migrations so measured sweeps keep outranking model picks instead
        of being silently orphaned:

        * pre-mesh entries (5 segments, no ``mesh`` segment) were all
          solved single-device — they ARE the ``mesh1x1`` picks;
        * pre-residency entries (no ``res=`` segment) were solved before
          residency was a pinnable axis — they ARE the ``res=auto`` picks
          (the solver now chooses the residency; a legacy measured tile_h
          keeps its priority and the residency is re-solved at that
          tile_h, see ``get_fused_schedule``);
        * pre-collective MBConv entries (no ``coll=`` segment) were
          solved before the projection-reduction layout was an axis —
          they ARE the ``coll=auto`` picks (the collective is re-solved
          at the entry's (tile_h, mode, residency); separable keys never
          grow the segment — that partitioning is collective-free);
        * pre-layout MBConv entries (no ``layout=`` segment) were all
          solved for a REPLICATED input arrival — the only entry form
          that existed — so they ARE the ``layout=replicated`` picks
          (unlike residency/collective this axis is a dataflow fact the
          caller states, not a solver choice, so there is no ``auto``);
        * pre-overlap MBConv entries (no ``ov=`` segment) were all
          solved for a SERIAL entry — pipelined entries did not exist,
          and a serial pick was feasibility-checked against the full
          VMEM budget where a pipelined solve halves it — so they ARE
          the ``ov=serial`` picks (like layout, the entry overlap is a
          dataflow fact the network DP states: no ``auto``);
        * pre-family MBConv entries (no ``act=``/``se=`` segments) were
          all solved for the classic EfficientNet block — silu
          activations, SE present (the only variant that existed) — so
          they ARE the ``act=silu|se=on`` picks.  The ``se=off`` and
          non-silu variants are NEW entry forms: an SE-carrying
          schedule's pick must never be echoed for a block whose pass 1
          vanishes (``fusedmb`` keys are born with every segment and
          never migrate)."""
        parts = key.split("|")
        if len(parts) == 5 and parts[0] in ("sep", "mbconv") \
                and not parts[3].startswith("mesh"):
            parts.insert(3, "mesh1x1")
        if len(parts) == 6 and parts[0] in ("sep", "mbconv") \
                and parts[3].startswith("mesh") \
                and not parts[4].startswith("res="):
            parts.insert(4, "res=auto")
        if len(parts) >= 7 and parts[0] == "mbconv" \
                and parts[3].startswith("mesh") \
                and parts[4].startswith("res=") \
                and not parts[5].startswith("coll="):
            parts.insert(5, "coll=auto")
        if len(parts) >= 8 and parts[0] == "mbconv" \
                and parts[4].startswith("res=") \
                and parts[5].startswith("coll=") \
                and not parts[6].startswith("layout="):
            parts.insert(6, "layout=replicated")
        if len(parts) >= 9 and parts[0] == "mbconv" \
                and parts[5].startswith("coll=") \
                and parts[6].startswith("layout=") \
                and not parts[7].startswith("ov="):
            parts.insert(7, "ov=serial")
        if len(parts) >= 10 and parts[0] == "mbconv" \
                and parts[6].startswith("layout=") \
                and parts[7].startswith("ov=") \
                and not parts[8].startswith("act="):
            parts.insert(8, "act=silu")
            parts.insert(9, "se=on")
        return "|".join(parts)

    def _load_disk(self) -> Dict[str, dict]:
        if self._disk is None:
            self._disk = {}
            if self.path is not None:
                try:
                    payload = json.loads(self.path.read_text())
                    if payload.get("version") == 1:
                        for k, v in payload.get("entries", {}).items():
                            new_k = self._migrate_key(k)
                            if new_k != k:
                                telemetry.counter(
                                    "schedule_cache.migrated_keys")
                            self._disk[new_k] = v
                except (OSError, ValueError):
                    pass                   # unreadable cache = empty cache
        return self._disk

    def _flush(self) -> None:
        if self.path is None:
            return
        try:
            self.directory.mkdir(parents=True, exist_ok=True)
            tmp = self.path.with_suffix(".tmp")
            tmp.write_text(json.dumps(
                {"version": 1, "entries": self._load_disk()},
                indent=1, sort_keys=True))
            tmp.replace(self.path)
        except OSError:
            pass                           # persistence is best-effort

    def get(self, key: str) -> Optional[dict]:
        hit = self._mem.get(key)
        if hit is not None:
            telemetry.counter("schedule_cache.hit.memory")
            return hit
        hit = self._load_disk().get(key)
        if hit is not None:
            telemetry.counter("schedule_cache.hit.disk")
            self._mem[key] = hit
        else:
            telemetry.counter("schedule_cache.miss")
        return hit

    def put(self, key: str, entry: dict, persist: bool = True) -> None:
        telemetry.counter("schedule_cache.put")
        self._mem[key] = entry
        if persist and self.path is not None:
            disk = self._load_disk()
            # never let a model pick clobber a measured entry (malformed
            # old entries — non-dicts — are overwritten, not honored)
            old = disk.get(key)
            if isinstance(old, dict) and old.get("source") == "measured" \
                    and entry.get("source") != "measured":
                return
            disk[key] = entry
            self._flush()

    def clear_memory(self) -> None:
        """Drop the in-process layer (tests: force a disk round-trip)."""
        self._mem.clear()
        self._disk = None


_SCHEDULE_CACHE: Optional[ScheduleCache] = None


def get_schedule_cache() -> ScheduleCache:
    global _SCHEDULE_CACHE
    if _SCHEDULE_CACHE is None:
        env = os.environ.get(_CACHE_DIR_ENV)
        _SCHEDULE_CACHE = ScheduleCache(Path(env) if env else None)
    return _SCHEDULE_CACHE


def set_schedule_cache_dir(directory: Optional[os.PathLike]) -> ScheduleCache:
    """Point the persistent schedule cache at ``directory`` (None = memory
    only).  Resets the in-process layer so the new directory is
    authoritative."""
    global _SCHEDULE_CACHE
    _SCHEDULE_CACHE = ScheduleCache(
        Path(directory) if directory is not None else None)
    return _SCHEDULE_CACHE


def _tpu_key(tpu: TPUConfig) -> str:
    """Every TPUConfig field enters the key: a schedule solved (and
    VMEM-checked) under one config must never be reused for another."""
    ths = "x".join(str(t) for t in tpu.tile_h_candidates)
    return f"vmem{tpu.vmem_bytes}-cb{tpu.c_block}-th{ths}"


def _res_segment(residency: Optional[str]) -> str:
    """Key segment for the REQUESTED residency: a pinned mode gets its own
    entry (its pick is solved under a different feasibility set); ``None``
    (the solver chooses) is the ``res=auto`` entry that legacy keys migrate
    into."""
    if residency is not None:
        validate_residency(residency)
    return f"res={residency or 'auto'}"


def _sep_key(shape: SeparableShape, tpu: TPUConfig,
             mesh_shape: MeshShape = (1, 1),
             residency: Optional[str] = None,
             in_layout: str = DEFAULT_LAYOUT,
             collective: str = DEFAULT_COLLECTIVE) -> str:
    """Schedule-cache key.  The EFFECTIVE mesh factors are part of the key:
    a schedule solved for one partitioning (per-device shard shapes, psum
    terms, VMEM headroom) must never be echoed for another — sharded and
    unsharded picks live in distinct entries.  Likewise the requested
    residency (``res=auto`` when the solver chooses).  The sharded-c_in
    entry form gets its own entries via an APPENDED segment (the default
    replicated key format — and its migration chain — is untouched; the
    classic separable partitioning is collective-free, so only the
    sharded-in form carries a collective)."""
    dp, mp = shard_factors(shape.b, shape.c_out, mesh_shape)
    suffix = ""
    if validate_layout(in_layout) != DEFAULT_LAYOUT:
        # the sharded-in form partitions on c_in, so its EFFECTIVE factors
        # differ from the base key's c_out-derived mesh segment
        dpi, mpi = shard_factors(shape.b, shape.c_in, mesh_shape)
        suffix = (f"|inlay={in_layout}"
                  f"|coll={validate_collective(collective)}"
                  f"|inmesh{dpi}x{mpi}")
    return (f"sep|b{shape.b}-h{shape.h}-w{shape.w}-ci{shape.c_in}"
            f"-co{shape.c_out}-k{shape.k}-s{shape.s}|dtb{shape.dtype_bytes}"
            f"|mesh{dp}x{mp}|{_res_segment(residency)}|{_tpu_key(tpu)}"
            f"|{_backend()}{suffix}")


def _coll_segment(collective: Optional[str]) -> str:
    """Key segment for the REQUESTED collective mode (``coll=auto`` when
    the solver chooses — the segment legacy MBConv keys migrate into)."""
    if collective is not None:
        validate_collective(collective)
    return f"coll={collective or 'auto'}"


def _layout_segment(in_layout: str) -> str:
    """Key segment for the input-layout the schedule is priced for.  This
    axis has no ``auto``: the arrival layout is a dataflow fact the caller
    (or the network-level DP) states — legacy keys migrate into
    ``layout=replicated``, the only entry form that existed."""
    return f"layout={validate_layout(in_layout)}"


def _overlap_segment(overlap: str) -> str:
    """Key segment for the entry-overlap the schedule is solved under.
    Like ``layout=`` this axis has no ``auto``: the network DP states
    whether a block's pass 1 streams behind the upstream pass 2 (which
    halves the VMEM budget its pass-1 footprint may claim) — legacy keys
    migrate into ``ov=serial``, the only entry form that existed."""
    return f"ov={validate_overlap(overlap)}"


def _act_segment(act: str) -> str:
    """Key segment for the block's activation variant.  No ``auto``: the
    act is a model fact the caller states — legacy keys migrate into
    ``act=silu`` (the only variant that existed)."""
    return f"act={validate_act(act)}"


def _se_segment(shape: MBConvShape) -> str:
    """Key segment for the SE axis, derived from the shape: ``se_ratio``
    never entered the legacy key, so an SE-less block would collide with
    the SE form of the same dims — a genuinely different solve (its pass
    1 can vanish entirely).  Legacy keys migrate into ``se=on``."""
    return f"se={'on' if shape.has_se else 'off'}"


def _mbconv_key(shape: MBConvShape, tpu: TPUConfig,
                mesh_shape: MeshShape = (1, 1),
                residency: Optional[str] = None,
                mode: Optional[str] = None,
                collective: Optional[str] = None,
                in_layout: str = DEFAULT_LAYOUT,
                overlap: str = DEFAULT_OVERLAP,
                act: str = DEFAULT_ACT) -> str:
    dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    # a pinned pass-2 mode gets its OWN entries (appended segment, so the
    # unpinned key format — and its migration chain — is untouched): a
    # tile_h/residency solved under one mode's VMEM footprint must never
    # be echoed for the other
    pin = f"|mode={mode}" if mode is not None else ""
    return (f"mbconv|b{shape.b}-h{shape.h}-w{shape.w}-ci{shape.c_in}"
            f"-cm{shape.c_mid}-co{shape.c_out}-k{shape.k}-s{shape.s}"
            f"|dtb{shape.dtype_bytes}|mesh{dp}x{mp}"
            f"|{_res_segment(residency)}|{_coll_segment(collective)}"
            f"|{_layout_segment(in_layout)}|{_overlap_segment(overlap)}"
            f"|{_act_segment(act)}|{_se_segment(shape)}"
            f"|{_tpu_key(tpu)}|{_backend()}{pin}")


def _entry_tile_h(hit, out_h: int):
    """Validated tile_h from a cache entry, or None if the entry is
    malformed or stale (a bad cache file must degrade to the model, never
    crash schedule lookup)."""
    try:
        tile_h = int(hit["tile_h"])
    except (TypeError, KeyError, ValueError):
        return None
    return tile_h if 1 <= tile_h <= out_h else None


def _entry_residency(hit) -> Optional[str]:
    """Validated residency from a cache entry; None for legacy entries
    (recorded before the residency axis) or malformed values — the caller
    then re-solves the residency at the entry's tile_h."""
    res = hit.get("residency") if isinstance(hit, dict) else None
    return res if res in RESIDENCY_MODES else None


def _entry_collective(hit) -> Optional[str]:
    """Validated collective mode from a cache entry; None for legacy
    entries (recorded before the collective axis) or malformed values —
    the caller then re-solves the collective at the entry's pick."""
    coll = hit.get("collective") if isinstance(hit, dict) else None
    return coll if coll in COLLECTIVE_MODES else None


# Solver preference among byte-identical collective modes: the ring
# all-reduce is the conservative default (output replicated, any consumer
# layout); ties essentially never occur — psum_scatter strictly undercuts
# the ring whenever the projection payload is nonzero.
_COLLECTIVE_RANK = {"ring_allreduce": 0, "psum_scatter": 1}


def _collective_set(shape: MBConvShape, eff: MeshShape,
                    collective: Optional[str]) -> Tuple[str, ...]:
    """Collective modes the solver may price at this partitioning.

    Off-mesh (effective model factor 1) the axis is degenerate: nothing
    crosses devices, so everything normalizes to the ring default — a
    scatter pin is meaningless there and is ignored rather than cached as
    a distinct non-schedule.  On-mesh, ``None`` enumerates the ring plus
    the psum_scatter pass-2 variant — non-dividing c_out no longer
    rejects a scatter: the kernel zero-pads the projection columns to
    the model factor and the model prices the padded payload
    (``perfmodel.scatter_c_out``)."""
    _dp, mp = eff
    if mp <= 1:
        return (DEFAULT_COLLECTIVE,)
    if collective is None:
        if can_psum_scatter(shape, eff):
            return COLLECTIVE_MODES
        return (DEFAULT_COLLECTIVE,)
    validate_collective(collective)
    return (collective,)


# ---------------------------------------------------------------------------
# separable (single-pass) schedules
# ---------------------------------------------------------------------------

def _f32_tile(*dims: int) -> int:
    return vmem_tile_bytes(dims, 4)


def vmem_footprint_bytes(shape: SeparableShape, tile_h: int,
                         tpu: TPUConfig,
                         residency: str = DEFAULT_RESIDENCY) -> int:
    """Modeled VMEM residency of one fused grid cell under one residency.

    Every array is priced at its (sublane, lane) tile padding
    (``perfmodel.vmem_tile_bytes``).  Counts the input staging (the
    strip-DMA slot buffer(s) — 2x for double-buffering — or the
    double-buffered full-height resident block), the double-buffered
    BlockSpec operands (both weight blocks and the output block), the f32
    PW scratch accumulator, and the tap loop's live f32 values (the DW
    accumulator, one tap slice and the PW partial): the budget the
    kernel's Mosaic rendering must respect.
    """
    ci = pick_channel_block(shape.c_in, tpu.c_block)
    co = _blocks(shape.c_out, tpu.c_block)
    tile_h = max(1, min(tile_h, shape.out_h))
    out_w = launch_width(shape.out_w, shape.s, shape.k, shape.padded_w)[0]
    db = shape.dtype_bytes
    x_win = separable_staging_bytes(shape, tile_h, residency, tpu.c_block)
    blocks = 2 * (vmem_tile_bytes((shape.k, shape.k, ci), db)
                  + vmem_tile_bytes((ci, co), db)
                  + vmem_tile_bytes((tile_h, out_w, co), db))
    pw_acc = _f32_tile(tile_h, out_w, co)
    temps = (2 * _f32_tile(tile_h, out_w, ci)
             + _f32_tile(tile_h * out_w, co))
    return x_win + blocks + pw_acc + temps


def _residency_set(residency: Optional[str]) -> Tuple[str, ...]:
    if residency is None:
        return RESIDENCY_MODES
    validate_residency(residency)
    return (residency,)


def candidate_schedules(
    shape: SeparableShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    in_layout: str = DEFAULT_LAYOUT, collective: str = DEFAULT_COLLECTIVE,
) -> Tuple[FusedSchedule, ...]:
    """All VMEM-feasible (tile_h, residency) schedules, model-priced.

    ``residency=None`` enumerates every staging mode (the solver's
    default); a pinned mode restricts the candidate set.  Under a mesh,
    feasibility and channel blocks are solved at the PER-DEVICE shard
    shape — batch/data with c_out/model for the default replicated entry,
    or c_in/model (full c_out, PW partial reduced per ``collective``) for
    the ``model_sharded`` entry form."""
    validate_layout(in_layout)
    local, eff = separable_shard(shape, mesh_shape, in_layout)
    ci = pick_channel_block(local.c_in, tpu.c_block)
    co = _blocks(local.c_out, tpu.c_block)
    out: list[FusedSchedule] = []
    seen = set()
    ths = [max(1, min(th, shape.out_h)) for th in tpu.tile_h_candidates]
    feasible = [(th, res) for th in ths for res in _residency_set(residency)
                if vmem_footprint_bytes(local, th, tpu, res)
                <= tpu.vmem_bytes]
    if not feasible:
        raise _infeasible("separable", local, tpu, residency=residency)
    for th, res in feasible:
        if (th, res) in seen:
            continue
        seen.add((th, res))
        out.append(FusedSchedule(
            tile_h=th, ci_block=ci, co_block=co,
            sharded=sharded_separable_traffic(shape, th, eff, tpu.c_block,
                                              res, in_layout, collective),
            staged=sharded_separable_staged_traffic(shape, th, eff,
                                                    tpu.c_block),
            residency=res,
        ))
    return tuple(out)


def select_fused_schedule(
    shape: SeparableShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    in_layout: str = DEFAULT_LAYOUT, collective: str = DEFAULT_COLLECTIVE,
) -> FusedSchedule:
    """Pick the (tile_h, residency) minimizing modeled total traffic —
    per-device HBM bytes across all devices plus collectives (ties ->
    larger tile_h: fewer grid cells, bigger MXU contractions; then the
    residency rank: double-buffered DMA > single-slot DMA > resident,
    since equal bytes moved earlier hide latency)."""
    cands = candidate_schedules(shape, tpu, mesh_shape, residency,
                                in_layout, collective)
    return min(cands, key=lambda c: (c.total_bytes, -c.tile_h,
                                     _RESIDENCY_RANK[c.residency]))


def _schedule_at(shape: SeparableShape, tile_h: int, tpu: TPUConfig,
                 mesh_shape: MeshShape = (1, 1),
                 residency: str = DEFAULT_RESIDENCY,
                 in_layout: str = DEFAULT_LAYOUT,
                 collective: str = DEFAULT_COLLECTIVE) -> FusedSchedule:
    local, eff = separable_shard(shape, mesh_shape, in_layout)
    return FusedSchedule(
        tile_h=tile_h,
        ci_block=pick_channel_block(local.c_in, tpu.c_block),
        co_block=_blocks(local.c_out, tpu.c_block),
        sharded=sharded_separable_traffic(shape, tile_h, eff, tpu.c_block,
                                          residency, in_layout, collective),
        staged=sharded_separable_staged_traffic(shape, tile_h, eff,
                                                tpu.c_block),
        residency=residency,
    )


def _solve_residency_at(shape: SeparableShape, tile_h: int, tpu: TPUConfig,
                        mesh_shape: MeshShape,
                        in_layout: str = DEFAULT_LAYOUT) -> str:
    """Best residency at a FIXED tile_h (legacy cache entries pin tile_h
    but predate the residency axis): min bytes among VMEM-feasible modes,
    ties broken by the residency rank."""
    local, eff = separable_shard(shape, mesh_shape, in_layout)
    modes = [res for res in RESIDENCY_MODES
             if vmem_footprint_bytes(local, tile_h, tpu, res)
             <= tpu.vmem_bytes]
    if not modes:
        raise _infeasible("separable", local, tpu, tile_h=tile_h)
    return min(modes, key=lambda res: (
        sharded_separable_traffic(shape, tile_h, eff, tpu.c_block, res,
                                  in_layout).device.total_bytes,
        _RESIDENCY_RANK[res]))


@_plan_span
def get_fused_schedule(
    b: int, h: int, w: int, c_in: int, c_out: int, k: int, s: int,
    dtype_bytes: int = 4, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    in_layout: str = DEFAULT_LAYOUT, collective: str = DEFAULT_COLLECTIVE,
) -> FusedSchedule:
    """Cached per-layer-shape schedule lookup (trace-time safe).

    Consults the in-process cache, then the JSON cache (where a measured
    sweep may have recorded ground truth), then the analytical model.
    ``mesh_shape`` is the ("data", "model") partitioning the schedule will
    run under and ``residency`` the requested staging pin (None = solver's
    choice) — both are cache-key axes, so different partitionings or pins
    never collide; the sharded-c_in entry form (``in_layout`` +
    ``collective``) gets its own appended key segments.  Legacy entries
    (pre-residency) keep their tile_h priority; the residency is
    re-solved at that tile_h."""
    shape = SeparableShape(b=b, h=h, w=w, c_in=c_in, c_out=c_out, k=k, s=s,
                           dtype_bytes=dtype_bytes)
    cache = get_schedule_cache()
    key = _sep_key(shape, tpu, mesh_shape, residency, in_layout, collective)
    hit = cache.get(key)
    tile_h = _entry_tile_h(hit, shape.out_h) if hit is not None else None
    if tile_h is not None:
        res = residency or _entry_residency(hit) \
            or _solve_residency_at(shape, tile_h, tpu, mesh_shape, in_layout)
        return _schedule_at(shape, tile_h, tpu, mesh_shape, res,
                            in_layout, collective)
    sched = select_fused_schedule(shape, tpu, mesh_shape, residency,
                                  in_layout, collective)
    cache.put(key, {"tile_h": sched.tile_h, "residency": sched.residency,
                    "source": "model", "recorded_at": time.time()})
    return sched


# ---------------------------------------------------------------------------
# MBConv (two-pass) schedules
# ---------------------------------------------------------------------------

def mbconv_vmem_footprint_bytes(shape: MBConvShape, tile_h: int,
                                tpu: TPUConfig,
                                residency: str = DEFAULT_RESIDENCY,
                                mode: str = "retain") -> int:
    """Modeled VMEM residency of one two-pass MBConv grid cell: the SUM of
    both passes' footprints (``mbconv_pass_vmem_bytes``).  The launches
    are separate, so the sum is deliberately conservative — a schedule
    that only fits one of them is not worth distinguishing."""
    return sum(mbconv_pass_vmem_bytes(shape, tile_h, tpu, residency, mode))


def mbconv_pass_vmem_bytes(shape: MBConvShape, tile_h: int,
                           tpu: TPUConfig,
                           residency: str = DEFAULT_RESIDENCY,
                           mode: str = "retain") -> Tuple[int, int]:
    """VMEM bytes of each pass's launch: ``(pass1, pass2)``, every array
    at its tile padding (``perfmodel.vmem_tile_bytes``).

    The expand+DW front end (pass 1, and pass 2 again under
    ``recompute``) holds the input staging, the f32 expand accumulator,
    the expand contraction's loaded window and f32 partial, the tap
    loop's live f32 values (accumulator, tap slice, masked pool copy) and
    the double-buffered expand/DW weight blocks.  Pass 1 adds its
    double-buffered outputs (the SE pool, the retained DW block); pass 2
    holds the retained-DW stream (``retain``) or the whole front end
    (``recompute``), plus the projection: f32 scratch and partial, the
    double-buffered gate, projection weight and output blocks.
    Cross-block pipelining co-resides block i's pass 2 with block i+1's
    pass 1, so the overlap feasibility check is per-pass against HALF the
    budget (``_OVERLAP_VMEM_DIV``).
    """
    ci = pick_channel_block(shape.c_in, tpu.c_block)
    cm = pick_channel_block(shape.c_mid, tpu.c_block)
    co = _blocks(shape.c_out, tpu.c_block)
    tile_h = max(1, min(tile_h, shape.out_h))
    in_rows = (tile_h - 1) * shape.s + shape.k
    out_w, w_tot = launch_width(shape.out_w, shape.s, shape.k,
                                shape.padded_w)
    db = shape.dtype_bytes
    # x-window staging only (the recompute form of the staging model);
    # the retain total adds the pass-2 DW re-read slots on top
    x_stage = mbconv_staging_bytes(shape, tile_h, "recompute", residency,
                                   tpu.c_block)
    dw_stage = mbconv_staging_bytes(shape, tile_h, mode, residency,
                                    tpu.c_block) - x_stage
    gate = 2 * _f32_tile(1, 1, cm) if shape.has_se else 0
    front = (x_stage + _f32_tile(in_rows, w_tot, cm)
             + _f32_tile(in_rows, w_tot, ci) + _f32_tile(in_rows, w_tot, cm)
             + 3 * _f32_tile(tile_h, out_w, cm)
             + 2 * (vmem_tile_bytes((ci, cm), db)
                    + vmem_tile_bytes((shape.k, shape.k, cm), db)))
    dw_out = (2 * vmem_tile_bytes((tile_h, out_w, cm), db)
              if mode == "retain" else 0)
    pass1 = front + gate + dw_out
    proj = (_f32_tile(tile_h, out_w, co) + _f32_tile(tile_h * out_w, co)
            + 2 * (vmem_tile_bytes((cm, co), db)
                   + vmem_tile_bytes((tile_h, out_w, co), db)) + gate)
    if mode == "retain":
        pass2 = dw_stage + _f32_tile(tile_h, out_w, cm) + proj
    else:
        pass2 = front + proj
    return pass1, pass2


# A pipelined entry co-resides two stages on one core (upstream pass 2 +
# this block's pass 1), so each stage may claim at most half the budget.
_OVERLAP_VMEM_DIV = 2


def _overlap_vmem_ok(shape: MBConvShape, tile_h: int, tpu: TPUConfig,
                     residency: str, mode: str) -> bool:
    """Pipelined-entry feasibility for THIS block's pass 1: it must fit
    the halved budget while the upstream pass 2 holds the other half.
    (The upstream side is checked symmetrically by the network DP.)"""
    p1, _p2 = mbconv_pass_vmem_bytes(shape, tile_h, tpu, residency, mode)
    return p1 <= tpu.vmem_bytes // _OVERLAP_VMEM_DIV


def candidate_mbconv_schedules(
    shape: MBConvShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    mode: Optional[str] = None, collective: Optional[str] = None,
    in_layout: str = DEFAULT_LAYOUT, overlap: str = DEFAULT_OVERLAP,
) -> Tuple[MBConvSchedule, ...]:
    """All VMEM-feasible (tile_h, mode, residency, collective) schedules,
    model-priced.

    A pinned ``mode`` restricts the candidate set, so tile_h/residency are
    solved (and VMEM-checked) under THAT mode's footprint — a retain pin
    must pay for the retained-DW stream buffers the recompute winner never
    carried.  Under a mesh, feasibility and channel blocks are solved at
    the per-device shard shape (batch/data, c_mid/model); the
    retain/recompute crossover therefore re-solves per partitioning — a
    shard's DW slice is mp-fold cheaper to retain than the whole expanded
    tensor.  The **collective** axis (projection reduction layout) only
    exists on-mesh: ring all-reduce always, psum_scatter on any on-mesh
    layer (non-dividing c_out pads to the model factor); it does not
    enter the VMEM check — both layouts run the identical kernels.

    ``in_layout`` is the ARRIVAL layout of the block input (a dataflow
    fact, not a solver axis): an identity-expand block consumes a
    ``model_sharded`` arrival collective-free with c_in sharded alongside
    c_mid (feasibility and channel blocks re-solved at the smaller
    shard), while a real expand prices the entry all-gather it must pay
    (``ShardedTraffic.transition_words``).

    ``overlap`` is, like the layout, a dataflow fact the network DP
    states: a ``pipelined`` entry co-resides this block's pass 1 with the
    upstream block's pass 2, so candidates must ALSO fit their pass-1
    footprint into half the VMEM budget (``_overlap_vmem_ok``) — a
    genuinely different feasibility set, hence a different solve."""
    if mode is not None and mode not in MBCONV_MODES:
        raise ValueError(mode)
    validate_layout(in_layout)
    validate_overlap(overlap)
    modes = MBCONV_MODES if mode is None else (mode,)
    local, eff = mbconv_shard(shape, mesh_shape, in_layout)
    colls = _collective_set(shape, eff, collective)
    ci = pick_channel_block(local.c_in, tpu.c_block)
    cm = pick_channel_block(local.c_mid, tpu.c_block)
    co = _blocks(local.c_out, tpu.c_block)
    out: list[MBConvSchedule] = []
    seen = set()
    ths = [max(1, min(th, shape.out_h)) for th in tpu.tile_h_candidates]
    combos = [(th, md, res)
              for th in ths for md in modes
              for res in _residency_set(residency)
              if mbconv_vmem_footprint_bytes(local, th, tpu, res, md)
              <= tpu.vmem_bytes
              and (overlap == DEFAULT_OVERLAP
                   or _overlap_vmem_ok(local, th, tpu, res, md))]
    if not combos:
        raise _infeasible("mbconv", local, tpu, residency=residency,
                          mode=mode, overlap=overlap)
    staged_cache: dict = {}
    for th, md, res in combos:
        for coll in colls:
            if (th, md, res, coll) in seen:
                continue
            seen.add((th, md, res, coll))
            if (th, coll) not in staged_cache:
                staged_cache[th, coll] = sharded_mbconv_staged_traffic(
                    shape, th, eff, tpu.c_block, coll, in_layout)
            out.append(MBConvSchedule(
                tile_h=th, mode=md, ci_block=ci, cm_block=cm, co_block=co,
                sharded=sharded_mbconv_traffic(shape, th, md, eff,
                                               tpu.c_block, res, coll,
                                               in_layout),
                staged=staged_cache[th, coll],
                residency=res, overlap=overlap,
            ))
    return tuple(out)


def select_mbconv_schedule(
    shape: MBConvShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    mode: Optional[str] = None, collective: Optional[str] = None,
    in_layout: str = DEFAULT_LAYOUT, overlap: str = DEFAULT_OVERLAP,
) -> MBConvSchedule:
    """Pick (tile_h, mode, residency, collective) minimizing modeled total
    two-pass traffic (ties -> larger tile_h, then retain: one DW
    round-trip beats recompute MACs; then the residency rank, then the
    ring default).  ``mode``/``residency``/``collective`` pins restrict
    the solve; ``in_layout`` states the arrival layout — and ``overlap``
    the entry overlap — the schedule must be priced/checked for."""
    cands = candidate_mbconv_schedules(shape, tpu, mesh_shape, residency,
                                       mode, collective, in_layout, overlap)
    return min(cands, key=lambda c: (c.total_bytes, -c.tile_h,
                                     c.mode != "retain",
                                     _RESIDENCY_RANK[c.residency],
                                     _COLLECTIVE_RANK[c.collective]))


def _mbconv_schedule_at(shape: MBConvShape, tile_h: int, mode: str,
                        tpu: TPUConfig, mesh_shape: MeshShape = (1, 1),
                        residency: str = DEFAULT_RESIDENCY,
                        collective: str = DEFAULT_COLLECTIVE,
                        in_layout: str = DEFAULT_LAYOUT,
                        overlap: str = DEFAULT_OVERLAP
                        ) -> MBConvSchedule:
    local, eff = mbconv_shard(shape, mesh_shape, in_layout)
    if eff[1] <= 1:
        collective = DEFAULT_COLLECTIVE   # degenerate axis: nothing crosses
        in_layout = DEFAULT_LAYOUT
    return MBConvSchedule(
        tile_h=tile_h, mode=mode,
        ci_block=pick_channel_block(local.c_in, tpu.c_block),
        cm_block=pick_channel_block(local.c_mid, tpu.c_block),
        co_block=_blocks(local.c_out, tpu.c_block),
        sharded=sharded_mbconv_traffic(shape, tile_h, mode, eff,
                                       tpu.c_block, residency, collective,
                                       in_layout),
        staged=sharded_mbconv_staged_traffic(shape, tile_h, eff,
                                             tpu.c_block, collective,
                                             in_layout),
        residency=residency, overlap=overlap,
    )


def _solve_mbconv_residency_at(shape: MBConvShape, tile_h: int, mode: str,
                               tpu: TPUConfig, mesh_shape: MeshShape,
                               in_layout: str = DEFAULT_LAYOUT) -> str:
    """Best residency at a FIXED (tile_h, mode) — see
    ``_solve_residency_at``.  Collective words are residency-invariant,
    so per-device bytes decide."""
    local, eff = mbconv_shard(shape, mesh_shape, in_layout)
    modes = [res for res in RESIDENCY_MODES
             if mbconv_vmem_footprint_bytes(local, tile_h, tpu, res, mode)
             <= tpu.vmem_bytes]
    if not modes:
        raise _infeasible("mbconv", local, tpu, tile_h=tile_h, mode=mode)
    return min(modes, key=lambda res: (
        sharded_mbconv_traffic(shape, tile_h, mode, eff, tpu.c_block,
                               res, in_layout=in_layout).device.total_bytes,
        _RESIDENCY_RANK[res]))


def _solve_mbconv_collective_at(shape: MBConvShape, tile_h: int, mode: str,
                                tpu: TPUConfig, mesh_shape: MeshShape,
                                residency: str,
                                in_layout: str = DEFAULT_LAYOUT) -> str:
    """Best collective at a FIXED (tile_h, mode, residency) — legacy
    cache entries predate the collective axis: min total bytes among the
    runnable layouts, ties to the ring default."""
    _local, eff = mbconv_shard(shape, mesh_shape, in_layout)
    return min(_collective_set(shape, eff, None), key=lambda coll: (
        sharded_mbconv_traffic(shape, tile_h, mode, eff, tpu.c_block,
                               residency, coll, in_layout).total_bytes,
        _COLLECTIVE_RANK[coll]))


@_plan_span
def get_mbconv_schedule(
    b: int, h: int, w: int, c_in: int, c_mid: int, c_out: int, k: int,
    s: int, se_ratio: float = 0.25, dtype_bytes: int = 4,
    tpu: TPUConfig = TPUConfig(), mesh_shape: MeshShape = (1, 1),
    residency: Optional[str] = None, mode: Optional[str] = None,
    collective: Optional[str] = None, in_layout: str = DEFAULT_LAYOUT,
    overlap: str = DEFAULT_OVERLAP, act: str = DEFAULT_ACT,
) -> MBConvSchedule:
    """Cached per-layer-shape two-pass schedule lookup (trace-time safe).

    ``mesh_shape`` and the requested ``residency``/``mode``/``collective``
    pins enter the cache key (see ``get_fused_schedule``): a pinned
    pass-2 mode solves tile_h and residency under that mode's VMEM
    footprint instead of echoing a schedule solved for the other mode,
    and a pinned collective prices (and caches) under that reduction
    layout only.  ``in_layout`` (the arrival layout — a dataflow fact the
    caller states) is a key axis too: a schedule feasibility-checked at
    the c_in-sharded entry shape must never be echoed for a replicated
    arrival.  Legacy entries keep their (tile_h, mode) priority with the
    residency — and, for pre-collective entries, the collective —
    re-solved at that point; pre-layout entries migrate into
    ``layout=replicated`` and pre-overlap entries into ``ov=serial``
    (the only entry forms that existed).  ``overlap`` — the entry
    overlap the network DP states — is a key axis for the same reason
    ``in_layout`` is: a pipelined entry's picks were feasibility-checked
    against the halved VMEM budget and must never be echoed for a serial
    entry (or vice versa).  ``act`` and the SE axis (derived from
    ``se_ratio``) are key segments too: an SE-less block's pass 1 can
    vanish entirely, so its picks live apart from the classic form's —
    legacy entries migrate into ``act=silu|se=on``, the only variant
    that existed, with no cold re-solve."""
    shape = MBConvShape(b=b, h=h, w=w, c_in=c_in, c_mid=c_mid, c_out=c_out,
                        k=k, s=s, se_ratio=se_ratio, dtype_bytes=dtype_bytes)
    cache = get_schedule_cache()
    key = _mbconv_key(shape, tpu, mesh_shape, residency, mode, collective,
                      in_layout, overlap, act)
    hit = cache.get(key)
    tile_h = _entry_tile_h(hit, shape.out_h) if hit is not None else None
    hit_mode = hit.get("mode") if isinstance(hit, dict) else None
    if tile_h is not None and hit_mode in MBCONV_MODES \
            and (mode is None or hit_mode == mode):
        res = residency or _entry_residency(hit) \
            or _solve_mbconv_residency_at(shape, tile_h, hit_mode, tpu,
                                          mesh_shape, in_layout)
        coll = collective or _entry_collective(hit) \
            or _solve_mbconv_collective_at(shape, tile_h, hit_mode, tpu,
                                           mesh_shape, res, in_layout)
        return _mbconv_schedule_at(shape, tile_h, hit_mode, tpu,
                                   mesh_shape, res, coll, in_layout,
                                   overlap)
    sched = select_mbconv_schedule(shape, tpu, mesh_shape, residency, mode,
                                   collective, in_layout, overlap)
    cache.put(key, {"tile_h": sched.tile_h, "mode": sched.mode,
                    "residency": sched.residency,
                    "collective": sched.collective,
                    "in_layout": sched.in_layout,
                    "overlap": sched.overlap, "source": "model",
                    "recorded_at": time.time()})
    return sched


# ---------------------------------------------------------------------------
# Fused-MBConv (single-pass) schedules
# ---------------------------------------------------------------------------

def _fusedmb_shape(b, h, w, c_in, c_mid, c_out, k, s,
                   dtype_bytes: int = 4) -> MBConvShape:
    """Fused-MBConv blocks reuse the MBConvShape vocabulary with
    ``se_ratio=0`` pinned (the family never carries SE)."""
    return MBConvShape(b=b, h=h, w=w, c_in=c_in, c_mid=c_mid, c_out=c_out,
                       k=k, s=s, se_ratio=0.0, dtype_bytes=dtype_bytes)


def _fusedmb_key(shape: MBConvShape, tpu: TPUConfig,
                 mesh_shape: MeshShape = (1, 1),
                 residency: Optional[str] = None,
                 collective: Optional[str] = None,
                 overlap: str = DEFAULT_OVERLAP,
                 act: str = DEFAULT_ACT) -> str:
    """Schedule-cache key for the Fused-MBConv family.  Born with every
    segment (``act=`` included) — there are no legacy fusedmb entries, so
    the key never migrates.  No ``layout=`` or ``se=`` segments: the
    entry is always replicated and the family never carries SE (both are
    family invariants, not axes)."""
    dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    return (f"fusedmb|b{shape.b}-h{shape.h}-w{shape.w}-ci{shape.c_in}"
            f"-cm{shape.c_mid}-co{shape.c_out}-k{shape.k}-s{shape.s}"
            f"|dtb{shape.dtype_bytes}|mesh{dp}x{mp}"
            f"|{_res_segment(residency)}|{_coll_segment(collective)}"
            f"|{_overlap_segment(overlap)}|{_act_segment(act)}"
            f"|{_tpu_key(tpu)}|{_backend()}")


def fusedmb_vmem_footprint_bytes(shape: MBConvShape, tile_h: int,
                                 tpu: TPUConfig,
                                 residency: str = DEFAULT_RESIDENCY) -> int:
    """Modeled VMEM residency of one single-pass Fused-MBConv grid cell,
    every array at its tile padding: the input staging, the f32 dense-conv
    and projection scratch accumulators (both live the whole cell — the
    conv output feeds the projection without leaving VMEM), the tap
    loop's live f32 values (tap slice, its product and the accumulator
    update, the projection partial) and the double-buffered weight and
    output blocks.  A block with no projection (expansion ratio 1) is
    priced as if it had one: an over-count of its VMEM and bytes."""
    ci = pick_channel_block(shape.c_in, tpu.c_block)
    cm = pick_channel_block(shape.c_mid, tpu.c_block)
    co = _blocks(shape.c_out, tpu.c_block)
    tile_h = max(1, min(tile_h, shape.out_h))
    out_w = launch_width(shape.out_w, shape.s, shape.k, shape.padded_w)[0]
    db, k = shape.dtype_bytes, shape.k
    staging = fusedmb_staging_bytes(shape, tile_h, residency, tpu.c_block)
    scratch = _f32_tile(tile_h, out_w, cm) + _f32_tile(tile_h, out_w, co)
    temps = (_f32_tile(tile_h, out_w, ci) + 2 * _f32_tile(tile_h * out_w, cm)
             + _f32_tile(tile_h * out_w, co))
    blocks = 2 * (vmem_tile_bytes((k, k, ci, cm), db)
                  + vmem_tile_bytes((cm, co), db)
                  + vmem_tile_bytes((tile_h, out_w, co), db))
    return staging + scratch + temps + blocks


def candidate_fusedmb_schedules(
    shape: MBConvShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    collective: Optional[str] = None, overlap: str = DEFAULT_OVERLAP,
) -> Tuple[FusedMBSchedule, ...]:
    """All VMEM-feasible (tile_h, residency, collective) single-pass
    schedules, model-priced.  A ``pipelined`` entry checks the WHOLE cell
    footprint against half the budget — the single pass IS the block's
    pass 1, so there is no cheaper per-pass split to co-reside."""
    validate_overlap(overlap)
    local, eff = fusedmb_shard(shape, mesh_shape)
    colls = _collective_set(shape, eff, collective)
    ci = pick_channel_block(local.c_in, tpu.c_block)
    cm = pick_channel_block(local.c_mid, tpu.c_block)
    co = _blocks(local.c_out, tpu.c_block)
    budget = tpu.vmem_bytes if overlap == DEFAULT_OVERLAP \
        else tpu.vmem_bytes // _OVERLAP_VMEM_DIV
    out: list[FusedMBSchedule] = []
    seen = set()
    ths = [max(1, min(th, shape.out_h)) for th in tpu.tile_h_candidates]
    feasible = [(th, res) for th in ths for res in _residency_set(residency)
                if fusedmb_vmem_footprint_bytes(local, th, tpu, res)
                <= budget]
    if not feasible:
        raise _infeasible("fusedmb", local, tpu, residency=residency,
                          overlap=overlap)
    staged_cache: dict = {}
    for th, res in feasible:
        for coll in colls:
            if (th, res, coll) in seen:
                continue
            seen.add((th, res, coll))
            if (th, coll) not in staged_cache:
                staged_cache[th, coll] = sharded_fusedmb_staged_traffic(
                    shape, th, eff, tpu.c_block, coll)
            out.append(FusedMBSchedule(
                tile_h=th, ci_block=ci, cm_block=cm, co_block=co,
                sharded=sharded_fusedmb_traffic(shape, th, eff, tpu.c_block,
                                                res, coll),
                staged=staged_cache[th, coll],
                residency=res, overlap=overlap,
            ))
    return tuple(out)


def select_fusedmb_schedule(
    shape: MBConvShape, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    collective: Optional[str] = None, overlap: str = DEFAULT_OVERLAP,
) -> FusedMBSchedule:
    """Pick (tile_h, residency, collective) minimizing modeled total
    traffic (ties -> larger tile_h, then the residency rank, then the
    ring default) — the MBConv objective minus the mode axis."""
    cands = candidate_fusedmb_schedules(shape, tpu, mesh_shape, residency,
                                        collective, overlap)
    return min(cands, key=lambda c: (c.total_bytes, -c.tile_h,
                                     _RESIDENCY_RANK[c.residency],
                                     _COLLECTIVE_RANK[c.collective]))


def _fusedmb_schedule_at(shape: MBConvShape, tile_h: int, tpu: TPUConfig,
                         mesh_shape: MeshShape = (1, 1),
                         residency: str = DEFAULT_RESIDENCY,
                         collective: str = DEFAULT_COLLECTIVE,
                         overlap: str = DEFAULT_OVERLAP) -> FusedMBSchedule:
    local, eff = fusedmb_shard(shape, mesh_shape)
    if eff[1] <= 1:
        collective = DEFAULT_COLLECTIVE   # degenerate axis: nothing crosses
    return FusedMBSchedule(
        tile_h=tile_h,
        ci_block=pick_channel_block(local.c_in, tpu.c_block),
        cm_block=pick_channel_block(local.c_mid, tpu.c_block),
        co_block=_blocks(local.c_out, tpu.c_block),
        sharded=sharded_fusedmb_traffic(shape, tile_h, eff, tpu.c_block,
                                        residency, collective),
        staged=sharded_fusedmb_staged_traffic(shape, tile_h, eff,
                                              tpu.c_block, collective),
        residency=residency, overlap=overlap,
    )


def _solve_fusedmb_residency_at(shape: MBConvShape, tile_h: int,
                                tpu: TPUConfig,
                                mesh_shape: MeshShape) -> str:
    """Best residency at a FIXED tile_h (cache entries whose residency
    field is missing or stale) — see ``_solve_residency_at``."""
    local, eff = fusedmb_shard(shape, mesh_shape)
    modes = [res for res in RESIDENCY_MODES
             if fusedmb_vmem_footprint_bytes(local, tile_h, tpu, res)
             <= tpu.vmem_bytes]
    if not modes:
        raise _infeasible("fusedmb", local, tpu, tile_h=tile_h)
    return min(modes, key=lambda res: (
        sharded_fusedmb_traffic(shape, tile_h, eff, tpu.c_block,
                                res).device.total_bytes,
        _RESIDENCY_RANK[res]))


def _solve_fusedmb_collective_at(shape: MBConvShape, tile_h: int,
                                 tpu: TPUConfig, mesh_shape: MeshShape,
                                 residency: str) -> str:
    """Best collective at a FIXED (tile_h, residency), ties to the ring
    default — see ``_solve_mbconv_collective_at``."""
    _local, eff = fusedmb_shard(shape, mesh_shape)
    return min(_collective_set(shape, eff, None), key=lambda coll: (
        sharded_fusedmb_traffic(shape, tile_h, eff, tpu.c_block,
                                residency, coll).total_bytes,
        _COLLECTIVE_RANK[coll]))


@_plan_span
def get_fusedmb_schedule(
    b: int, h: int, w: int, c_in: int, c_mid: int, c_out: int, k: int,
    s: int, dtype_bytes: int = 4, tpu: TPUConfig = TPUConfig(),
    mesh_shape: MeshShape = (1, 1), residency: Optional[str] = None,
    collective: Optional[str] = None, overlap: str = DEFAULT_OVERLAP,
    act: str = DEFAULT_ACT,
) -> FusedMBSchedule:
    """Cached per-layer-shape single-pass schedule lookup (trace-time
    safe) for the Fused-MBConv family — the third pipeline next to
    ``get_fused_schedule`` (separable) and ``get_mbconv_schedule``.  Same
    cache discipline: mesh, pins, overlap and act are key axes; the
    family has no mode (single pass), no se (never carried) and no
    layout (always replicated) axis."""
    shape = _fusedmb_shape(b, h, w, c_in, c_mid, c_out, k, s, dtype_bytes)
    cache = get_schedule_cache()
    key = _fusedmb_key(shape, tpu, mesh_shape, residency, collective,
                       overlap, act)
    hit = cache.get(key)
    tile_h = _entry_tile_h(hit, shape.out_h) if hit is not None else None
    if tile_h is not None:
        res = residency or _entry_residency(hit) \
            or _solve_fusedmb_residency_at(shape, tile_h, tpu, mesh_shape)
        coll = collective or _entry_collective(hit) \
            or _solve_fusedmb_collective_at(shape, tile_h, tpu, mesh_shape,
                                            res)
        return _fusedmb_schedule_at(shape, tile_h, tpu, mesh_shape, res,
                                    coll, overlap)
    sched = select_fusedmb_schedule(shape, tpu, mesh_shape, residency,
                                    collective, overlap)
    cache.put(key, {"tile_h": sched.tile_h, "residency": sched.residency,
                    "collective": sched.collective, "source": "model",
                    "recorded_at": time.time()})
    return sched


# ---------------------------------------------------------------------------
# network-level layout solving (MIREDO-style chain DP)
#
# PR 5's per-layer solver flips every on-mesh B0 block to psum_scatter —
# but a per-layer pick cannot see that no consumer keeps the c_out-sharded
# output, so chained blocks silently repay the all-gather at the next
# entry and the scatter win cancels exactly (scatter + repay-gather ==
# ring, word for word — the collective accounting makes that an identity,
# not an estimate).  The DP below solves the CHAIN: states are boundary
# layouts, per-element costs come from ``select_mbconv_schedule`` under
# pinned (collective, in_layout), and boundary transitions are priced by
# ``perfmodel.layout_transition_words``.  The strict network-level win
# comes from the two places the tie theorem does not apply:
#
# * the stem boundary — a model-sharded stem output is materialized once
#   per element instead of once per device of each model group, and
# * identity-expand consumers (B0's block0 is the only e == 1 block) —
#   their entry takes a c_in-sharded arrival collective-free with every
#   pass-1 strip read shrunk by the model factor.
#
# Every e > 1 boundary provably ties: the dense expand needs ALL of c_in
# on every device, so a sharded arrival must be gathered back (priced as
# ``transition_words``), and scatter+gather == ring.  The DP therefore
# keeps interior boundaries replicated (ring exits) and shards exactly
# the boundaries that pay — reversing PR 5's scatter-everywhere greedy.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BlockRow:
    """One family-generic network-chain element: the block FAMILY is data
    on the row, not code in the solver.  Legacy 7-tuples (h, w, c_in,
    c_mid, c_out, k, s) remain accepted everywhere rows are consumed and
    mean ``family="mbconv"`` at the chain-wide ``se_ratio`` — BlockRow is
    how a chain mixes families (EfficientNet-V2's fused stages + MBConv
    tail) and per-block act/SE variants (MobileNet-V3) in one solve."""

    h: int
    w: int
    c_in: int
    c_mid: int
    c_out: int
    k: int
    s: int
    family: str = "mbconv"       # "mbconv" | "fusedmb"
    act: str = DEFAULT_ACT
    se_ratio: float = 0.25       # <= 0 means no SE; ignored for fusedmb

    def __post_init__(self):
        if self.family not in CHAIN_FAMILIES:
            raise ValueError(
                f"family must be one of {CHAIN_FAMILIES}, "
                f"got {self.family!r}")
        validate_act(self.act)
        if self.family == "fusedmb" and self.se_ratio > 0:
            # the family never carries SE — normalize rather than trip
            # every table builder over the default
            object.__setattr__(self, "se_ratio", 0.0)


@dataclass(frozen=True)
class BlockPlan:
    """One chain element's solved assignment inside a ``NetworkPlan``."""

    index: int
    shape: MBConvShape
    in_layout: str               # arrival layout the entry consumes
    out_layout: str              # layout the output leaves in
    # per-layer solve under the pinned axes: MBConvSchedule for the
    # two-pass family, FusedMBSchedule for the single-pass one
    schedule: "MBConvSchedule | FusedMBSchedule"
    boundary_words: int          # all-gather repay paid AT this entry
    # overlap of the boundary ENTERING this block (upstream pass 2 vs
    # this block's pass 1); "pipelined" only where the annotation pass
    # proved eligibility — see ``_annotate_overlap``
    entry_overlap: str = DEFAULT_OVERLAP
    # the per-pass cost split the latency accessors price (filled by the
    # solvers; None for hand-built plans, re-derived lazily)
    pass_costs: Optional[MBConvPassCosts] = None
    family: str = "mbconv"       # which pipeline runs this element
    act: str = DEFAULT_ACT       # activation variant (model fact)

    @property
    def boundary_bytes(self) -> int:
        return self.boundary_words * self.shape.dtype_bytes


@dataclass(frozen=True)
class NetworkPlan:
    """A solved (or greedy-reference) layout chain for a block sequence.

    The chain is the stem output plus every MBConv block: the stem is
    element 0 of the dataflow (its output materialization is priced per
    layout — a replicated stem writes the full activation on every device
    of each model group; a sharded one writes each element once), then
    each block carries its per-layer schedule plus the boundary repay its
    entry paid.  ``head_boundary_words`` is the final repay when the last
    block's output leaves sharded but the head consumes replicated."""

    mesh_shape: MeshShape
    stem_layout: str
    stem_words: int              # stem output materialization, mesh-wide
    blocks: Tuple[BlockPlan, ...]
    head_boundary_words: int
    dtype_bytes: int = 4
    policy: str = "solved"       # "solved" (DP) | "greedy" (per-layer)

    @property
    def stem_bytes(self) -> int:
        return self.stem_words * self.dtype_bytes

    @property
    def block_bytes(self) -> int:
        return sum(p.schedule.total_bytes for p in self.blocks)

    @property
    def boundary_words(self) -> int:
        return (sum(p.boundary_words for p in self.blocks)
                + self.head_boundary_words)

    @property
    def transition_bytes(self) -> int:
        """All layout-transition bytes in the chain: the boundary repays
        (including the head's) plus any entry-internal gathers the
        per-layer schedules carry."""
        return (self.boundary_words * self.dtype_bytes
                + sum(p.schedule.transition_bytes for p in self.blocks))

    @property
    def total_bytes(self) -> int:
        return (self.stem_bytes + self.block_bytes
                + self.boundary_words * self.dtype_bytes)

    @property
    def sharded_pairs(self) -> Tuple[Tuple[int, int], ...]:
        """Adjacent chain pairs whose boundary STAYS sharded (producer
        leaves model_sharded, consumer enters model_sharded).  Indices
        are chain positions with the stem as -1."""
        pairs = []
        prev_idx, prev_lay = -1, self.stem_layout
        for p in self.blocks:
            if prev_lay == "model_sharded" and p.in_layout == "model_sharded":
                pairs.append((prev_idx, p.index))
            prev_idx, prev_lay = p.index, p.out_layout
        return tuple(pairs)

    # -- overlap-aware latency accessors -----------------------------------
    #
    # The byte DP above stays the primary objective; latency is priced on
    # top of the solved plan from the fitted PerfCoefficients applied to
    # each block's per-pass cost split.  The stem is not a two-pass block
    # and is not priced here — these totals compare the SAME chain
    # serialized vs pipelined, which is the only comparison the overlap
    # axis decides.

    @property
    def pipelined_boundaries(self) -> Tuple[int, ...]:
        """Block indices whose ENTRY boundary pipelines (block i-1's
        pass 2 overlapping block i's pass 1; stem→block0 never appears —
        the stem is not a two-pass producer)."""
        return tuple(p.index for p in self.blocks
                     if p.entry_overlap == "pipelined")

    def _costs(self, p: BlockPlan) -> MBConvPassCosts:
        if p.pass_costs is not None:
            return p.pass_costs
        sch = p.schedule
        if p.family == "fusedmb":
            return sharded_fusedmb_pass_costs(
                p.shape, sch.tile_h, self.mesh_shape, 128,
                sch.residency, sch.collective)
        return sharded_mbconv_pass_costs(
            p.shape, sch.tile_h, sch.mode, self.mesh_shape, 128,
            sch.residency, sch.collective, sch.in_layout)

    def block_pass_us(self, index: int,
                      coeffs: Optional[PerfCoefficients] = None
                      ) -> Tuple[float, float]:
        """Calibrated (pass1_us, pass2_us) of one chain block."""
        coeffs = coeffs or get_perf_coefficients()
        pc = self._costs(self.blocks[index])
        return (mbconv_pass_us(coeffs, pc.pass1, pc.pass1_collective_words),
                mbconv_pass_us(coeffs, pc.pass2, pc.pass2_collective_words))

    def serial_latency_us(self,
                          coeffs: Optional[PerfCoefficients] = None
                          ) -> float:
        """Modeled chain latency with every boundary serialized (every
        pass of every block paid in full, back to back)."""
        coeffs = coeffs or get_perf_coefficients()
        return sum(sum(self.block_pass_us(i, coeffs))
                   for i in range(len(self.blocks)))

    def pipelined_latency_us(self,
                             coeffs: Optional[PerfCoefficients] = None
                             ) -> float:
        """Modeled chain latency honoring the solved ``entry_overlap``
        marks: each pipelined boundary pays max(prev pass 2, next pass 1)
        instead of their sum — i.e. the serial total minus the hidden
        min.  Structurally <= ``serial_latency_us`` (both terms are
        nonnegative), equal iff nothing pipelines."""
        coeffs = coeffs or get_perf_coefficients()
        total = self.serial_latency_us(coeffs)
        for i in range(1, len(self.blocks)):
            if self.blocks[i].entry_overlap != "pipelined":
                continue
            _p1_prev, p2_prev = self.block_pass_us(i - 1, coeffs)
            p1_cur, _p2_cur = self.block_pass_us(i, coeffs)
            total -= min(p2_prev, p1_cur)
        return total

    def boundary_latencies(self,
                           coeffs: Optional[PerfCoefficients] = None
                           ) -> Tuple[dict, ...]:
        """Per-interior-boundary latency table (block i-1 → block i):
        the two overlapped pass terms, the serialized and
        overlap-honoring boundary costs, and the solved overlap mark."""
        coeffs = coeffs or get_perf_coefficients()
        out = []
        for i in range(1, len(self.blocks)):
            _p1, p2_prev = self.block_pass_us(i - 1, coeffs)
            p1_cur, _p2 = self.block_pass_us(i, coeffs)
            ov = self.blocks[i].entry_overlap
            out.append({
                "boundary": (self.blocks[i - 1].index, self.blocks[i].index),
                "pass2_us": p2_prev, "pass1_us": p1_cur,
                "serialized_us": boundary_overlap_us(p2_prev, p1_cur,
                                                     "serial"),
                "overlap_us": boundary_overlap_us(p2_prev, p1_cur, ov),
                "overlap": ov,
            })
        return tuple(out)


def _stem_words(b: int, h: int, w: int, c: int, mesh_shape: MeshShape,
                layout: str) -> int:
    """Mesh-wide words the stem output materializes under one boundary
    layout.  Replicated: every device of each model group writes its data
    group's full (B_local, H, W, C) activation — mp copies of the tensor.
    Model-sharded: each element is written exactly once mesh-wide.  Batch
    is assumed data-divisible (it is for every B0 bench shape); the model
    factor only applies when the stem channels actually divide."""
    validate_layout(layout)
    dp, mp = shard_factors(b, c, mesh_shape)
    full = b * h * w * c
    if layout == "model_sharded" and mp > 1:
        return full
    return full * max(1, mesh_shape[1])


def _chain_shapes(rows: Sequence, b: int,
                  se_ratio: float, dtype_bytes: int
                  ) -> Tuple[Tuple[MBConvShape, str, str], ...]:
    """Normalize chain rows to (shape, family, act) triples.

    Rows may be legacy (h, w, c_in, c_mid, c_out, k, s) tuples — MBConv
    at the chain-wide ``se_ratio``, silu — or family-generic
    ``BlockRow``s carrying their own family/act/se_ratio.  Both forms mix
    freely in one chain."""
    out = []
    for row in rows:
        if isinstance(row, BlockRow):
            out.append((
                MBConvShape(b=b, h=row.h, w=row.w, c_in=row.c_in,
                            c_mid=row.c_mid, c_out=row.c_out, k=row.k,
                            s=row.s, se_ratio=row.se_ratio,
                            dtype_bytes=dtype_bytes),
                row.family, row.act))
        else:
            h, w, ci, cm, co, k, s = row
            out.append((
                MBConvShape(b=b, h=h, w=w, c_in=ci, c_mid=cm, c_out=co,
                            k=k, s=s, se_ratio=se_ratio,
                            dtype_bytes=dtype_bytes),
                "mbconv", DEFAULT_ACT))
    return tuple(out)


def network_rows_from_table(
    table: Sequence[Tuple[int, int, int, int, int, int]]
) -> Tuple[Tuple[int, int, int, int, int, int, int], ...]:
    """Adapt a ``core.workloads`` MBConv table — rows of (c_in, c_out,
    expand_ratio, k, s, ifmap hw) — into the (h, w, c_in, c_mid, c_out,
    k, s) chain rows the network solver consumes."""
    return tuple((hw, hw, ci, ci * e, co, k, s)
                 for ci, co, e, k, s, hw in table)


def _allowed_in_layouts(shape: MBConvShape,
                        mesh_shape: MeshShape) -> Tuple[str, ...]:
    """Arrival layouts worth offering the DP: replicated always; a
    model-sharded arrival only where the entry consumes it collective-free
    (identity expand — a real expand's entry gather makes sharded-in
    byte-identical to a boundary repay, so enumerating it only duplicates
    the replicated state)."""
    if can_shard_input(shape, mesh_shape):
        return (DEFAULT_LAYOUT, "model_sharded")
    return (DEFAULT_LAYOUT,)


def _allowed_out_layouts(shape: MBConvShape,
                         mesh_shape: MeshShape) -> Tuple[str, ...]:
    _dp, mp = shard_factors(shape.b, shape.c_mid, mesh_shape)
    if mp > 1:
        return (DEFAULT_LAYOUT, "model_sharded")
    return (DEFAULT_LAYOUT,)


def _block_pass_costs(shape: MBConvShape, sch, mesh_shape: MeshShape,
                      tpu: TPUConfig,
                      family: str = "mbconv") -> MBConvPassCosts:
    if family == "fusedmb":
        return sharded_fusedmb_pass_costs(
            shape, sch.tile_h, mesh_shape, tpu.c_block,
            sch.residency, sch.collective)
    return sharded_mbconv_pass_costs(
        shape, sch.tile_h, sch.mode, mesh_shape, tpu.c_block,
        sch.residency, sch.collective, sch.in_layout)


def _annotate_overlap(plan: NetworkPlan, tpu: TPUConfig,
                      coeffs: Optional[PerfCoefficients] = None
                      ) -> NetworkPlan:
    """Mark every chain boundary that can pipeline (the overlap axis).

    The byte DP stays untouched — overlap never changes what moves, only
    when, so it is annotated on the solved chain per boundary (the
    per-boundary savings are separable, which makes greedy per-boundary
    marking optimal).  Boundary i-1 → i pipelines iff ALL of:

    * no boundary repay and no entry-internal gather at block i's entry —
      an all-gather is a barrier the consumer's first strip must wait on;
    * the producer's pass-2 VMEM occupancy fits half the budget (retain
      pass 2 holds only the DW re-read stream + projection terms; a
      recompute pass 2 re-runs the whole front end and occupies its full
      cell footprint);
    * re-solving block i under ``overlap="pipelined"`` (pass-1 footprint
      against the halved budget, same collective/in_layout pins) finds a
      schedule with EQUAL total bytes — latency is secondary to the DP's
      byte objective, a boundary never buys overlap with extra traffic —
      and the same out_layout (the downstream chain must be unaffected);
    * the overlap actually hides time at the calibration: min(pass2_us,
      pass1_us) > 0.

    Blocks that stay serial keep their DP schedules; pipelined blocks
    carry the byte-equal pipelined re-solve (its ``ov=pipelined`` cache
    entries live under their own key segment)."""
    coeffs = coeffs or get_perf_coefficients()
    blocks = list(plan.blocks)
    half = tpu.vmem_bytes // _OVERLAP_VMEM_DIV
    for i in range(1, len(blocks)):
        prev, cur = blocks[i - 1], blocks[i]
        if prev.family == "fusedmb":
            # single-pass producer: its "pass 2" is exactly zero — there
            # is no compute for the consumer's pass-1 DMA to hide behind,
            # so the boundary stays honestly serial (the calibrated
            # min(p2, p1) == 0 guard below would catch this too; skipping
            # here keeps the mode/vmem probing two-pass-only)
            continue
        if cur.boundary_words != 0 or cur.schedule.transition_bytes != 0:
            continue
        psch = prev.schedule
        local_prev, _eff = mbconv_shard(prev.shape, plan.mesh_shape,
                                        psch.in_layout)
        if psch.mode == "retain":
            _p1v, p2_vmem = mbconv_pass_vmem_bytes(
                local_prev, psch.tile_h, tpu, psch.residency, psch.mode)
        else:
            p2_vmem = mbconv_vmem_footprint_bytes(
                local_prev, psch.tile_h, tpu, psch.residency, psch.mode)
        if p2_vmem > half:
            continue
        try:
            if cur.family == "fusedmb":
                # a single-pass CONSUMER can still stream behind a
                # two-pass producer's pass 2 — its whole cell is the
                # pass-1 footprint the halved budget must fit
                resolved = select_fusedmb_schedule(
                    cur.shape, tpu, plan.mesh_shape,
                    collective=cur.schedule.collective,
                    overlap="pipelined")
            else:
                resolved = select_mbconv_schedule(
                    cur.shape, tpu, plan.mesh_shape,
                    collective=cur.schedule.collective,
                    in_layout=cur.in_layout, overlap="pipelined")
        except VMEMInfeasibleError:
            continue      # nothing fits the halved budget: stays serial
        if (resolved.total_bytes != cur.schedule.total_bytes
                or resolved.out_layout != cur.out_layout):
            continue
        prev_costs = plan._costs(prev)
        cur_costs = _block_pass_costs(cur.shape, resolved,
                                      plan.mesh_shape, tpu, cur.family)
        p2_us = mbconv_pass_us(coeffs, prev_costs.pass2,
                               prev_costs.pass2_collective_words)
        p1_us = mbconv_pass_us(coeffs, cur_costs.pass1,
                               cur_costs.pass1_collective_words)
        if min(p2_us, p1_us) <= 0.0:
            continue
        blocks[i] = replace(cur, schedule=resolved,
                            entry_overlap="pipelined",
                            pass_costs=cur_costs)
        telemetry.counter("autotune.network_plan.pipelined_boundary")
    return replace(plan, blocks=tuple(blocks))


def solve_network_schedule(
    rows: Sequence[Tuple[int, ...]], b: int,
    mesh_shape: MeshShape = (1, 1), tpu: TPUConfig = TPUConfig(),
    dtype_bytes: int = 4, se_ratio: float = 0.25,
) -> NetworkPlan:
    """DP over the block chain picking per-block (residency, collective,
    in-layout, out-layout) jointly to minimize total modeled bytes.

    ``rows`` are legacy (h, w, c_in, c_mid, c_out, k, s) tuples (see
    ``network_rows_from_table``) or family-generic ``BlockRow``s — the
    two forms mix freely, so an EfficientNet-V2 chain states its fused
    stages next to its MBConv tail and a MobileNet-V3 chain states
    per-block act/SE; the stem boundary is seeded from the first block's
    input.  States are boundary layouts; each (state, in-layout,
    out-layout) candidate prices as the boundary transition plus the
    per-layer solve under the pinned (collective, in_layout) — tile_h,
    mode and residency re-solved by the family's selector inside the pin
    (``select_mbconv_schedule`` or ``select_fusedmb_schedule``; the
    fusedmb entry is replicated-only, so a sharded arrival repays at the
    boundary and the DP sees that price).  Byte ties prefer replicated
    boundaries (candidates are enumerated replicated-first and only a
    STRICT improvement replaces a state), so the plan shards exactly the
    boundaries that pay.

    After the byte DP, ``_annotate_overlap`` marks the boundaries that
    can pipeline (upstream pass 2 overlapping the consumer's pass 1) —
    bytes first, then hide what latency the calibration says can hide;
    a single-pass producer's boundary never pipelines (zero pass 2)."""
    chain = _chain_shapes(rows, b, se_ratio, dtype_bytes)
    if not chain:
        raise ValueError("network solve needs at least one block row")
    first = chain[0][0]
    h0, w0, c0 = first.h, first.w, first.c_in
    _dp0, mp0 = shard_factors(b, c0, mesh_shape)
    stem_opts = [DEFAULT_LAYOUT] + (["model_sharded"] if mp0 > 1 else [])
    # state: boundary layout -> (cost bytes, stem layout, block plans)
    states: Dict[str, tuple] = {}
    for lay in stem_opts:
        cost = _stem_words(b, h0, w0, c0, mesh_shape, lay) * dtype_bytes
        cur = states.get(lay)
        if cur is None or cost < cur[0]:
            states[lay] = (cost, lay, ())
    prev_dims = (h0, w0, c0)
    for i, (shape, family, act) in enumerate(chain):
        in_lays = ((DEFAULT_LAYOUT,) if family == "fusedmb"
                   else _allowed_in_layouts(shape, mesh_shape))
        new_states: Dict[str, tuple] = {}
        for prev_lay, (cost, stem_lay, plans) in states.items():
            for in_lay in in_lays:
                bwords = layout_transition_words(
                    b, prev_dims[0], prev_dims[1], prev_dims[2],
                    mesh_shape, prev_lay, in_lay)
                for out_lay in _allowed_out_layouts(shape, mesh_shape):
                    coll = ("psum_scatter" if out_lay == "model_sharded"
                            else DEFAULT_COLLECTIVE)
                    if family == "fusedmb":
                        sch = select_fusedmb_schedule(
                            shape, tpu, mesh_shape, collective=coll)
                    else:
                        sch = select_mbconv_schedule(
                            shape, tpu, mesh_shape, collective=coll,
                            in_layout=in_lay)
                    total = (cost + bwords * dtype_bytes + sch.total_bytes)
                    plan = BlockPlan(
                        index=i, shape=shape, in_layout=sch.in_layout,
                        out_layout=sch.out_layout, schedule=sch,
                        boundary_words=bwords,
                        pass_costs=_block_pass_costs(shape, sch,
                                                     mesh_shape, tpu,
                                                     family),
                        family=family, act=act)
                    cur = new_states.get(sch.out_layout)
                    if cur is None or total < cur[0]:
                        new_states[sch.out_layout] = (
                            total, stem_lay, plans + (plan,))
        states = new_states
        prev_dims = (shape.out_h, shape.out_w, shape.c_out)
    best = None
    for lay, (cost, stem_lay, plans) in states.items():
        head_words = layout_transition_words(
            b, prev_dims[0], prev_dims[1], prev_dims[2], mesh_shape,
            lay, DEFAULT_LAYOUT)
        total = cost + head_words * dtype_bytes
        if best is None or total < best[0]:
            best = (total, stem_lay, plans, head_words)
    total, stem_lay, plans, head_words = best
    plan = NetworkPlan(
        mesh_shape=mesh_shape, stem_layout=stem_lay,
        stem_words=_stem_words(b, h0, w0, c0, mesh_shape, stem_lay),
        blocks=plans, head_boundary_words=head_words,
        dtype_bytes=dtype_bytes, policy="solved")
    assert plan.total_bytes == total   # the parts must re-sum to the DP cost
    plan = _annotate_overlap(plan, tpu)
    assert plan.total_bytes == total   # overlap moves time, never bytes
    return plan


def greedy_network_schedule(
    rows: Sequence[Tuple[int, ...]], b: int,
    mesh_shape: MeshShape = (1, 1), tpu: TPUConfig = TPUConfig(),
    dtype_bytes: int = 4, se_ratio: float = 0.25,
) -> NetworkPlan:
    """The per-layer reference the DP is gated against: every block solved
    in isolation (the PR-5 status quo — replicated arrivals, collective
    chosen per layer, so every on-mesh block flips to psum_scatter), the
    stem replicated, and every sharded exit silently repaying its
    all-gather at the next (replicated) entry."""
    chain = _chain_shapes(rows, b, se_ratio, dtype_bytes)
    if not chain:
        raise ValueError("network solve needs at least one block row")
    first = chain[0][0]
    h0, w0, c0 = first.h, first.w, first.c_in
    plans = []
    prev_lay, prev_dims = DEFAULT_LAYOUT, (h0, w0, c0)
    for i, (shape, family, act) in enumerate(chain):
        if family == "fusedmb":
            sch = select_fusedmb_schedule(shape, tpu, mesh_shape)
        else:
            sch = select_mbconv_schedule(shape, tpu, mesh_shape)
        bwords = layout_transition_words(
            b, prev_dims[0], prev_dims[1], prev_dims[2], mesh_shape,
            prev_lay, DEFAULT_LAYOUT)
        plans.append(BlockPlan(
            index=i, shape=shape, in_layout=DEFAULT_LAYOUT,
            out_layout=sch.out_layout, schedule=sch,
            boundary_words=bwords,
            pass_costs=_block_pass_costs(shape, sch, mesh_shape, tpu,
                                         family),
            family=family, act=act))
        prev_lay = sch.out_layout
        prev_dims = (shape.out_h, shape.out_w, shape.c_out)
    head_words = layout_transition_words(
        b, prev_dims[0], prev_dims[1], prev_dims[2], mesh_shape,
        prev_lay, DEFAULT_LAYOUT)
    return NetworkPlan(
        mesh_shape=mesh_shape, stem_layout=DEFAULT_LAYOUT,
        stem_words=_stem_words(b, h0, w0, c0, mesh_shape, DEFAULT_LAYOUT),
        blocks=tuple(plans), head_boundary_words=head_words,
        dtype_bytes=dtype_bytes, policy="greedy")


@lru_cache(maxsize=64)
def _network_plan_cached(rows: tuple, b: int, mesh_shape: MeshShape,
                         dtype_bytes: int, se_ratio: float,
                         tpu: TPUConfig) -> NetworkPlan:
    return solve_network_schedule(rows, b, mesh_shape, tpu, dtype_bytes,
                                  se_ratio)


@_plan_span
def get_network_plan(
    rows: Sequence[Tuple[int, ...]], b: int,
    mesh_shape: MeshShape = (1, 1), dtype_bytes: int = 4,
    se_ratio: float = 0.25, tpu: TPUConfig = TPUConfig(),
) -> NetworkPlan:
    """Trace-time-safe cached network solve (the in-process layer; the
    per-block schedules the plan pins are themselves persisted through
    the regular schedule cache under their ``layout=`` keys when the
    model layer executes the plan).  Counters distinguish a fresh DP
    solve from a cache reuse — the vision serving engine leans on reuse
    being the steady state (one solve per resolution bucket, then every
    batch of that bucket replays it)."""
    misses_before = _network_plan_cached.cache_info().misses
    frozen_rows = tuple(r if isinstance(r, BlockRow) else tuple(r)
                        for r in rows)
    plan = _network_plan_cached(frozen_rows, b, tuple(mesh_shape),
                                dtype_bytes, se_ratio, tpu)
    solved = _network_plan_cached.cache_info().misses > misses_before
    telemetry.counter("autotune.network_plan.solve" if solved
                      else "autotune.network_plan.reuse")
    return plan


# ---------------------------------------------------------------------------
# measured fallback
# ---------------------------------------------------------------------------

def benchmark_fused_sweep(
    x, w_dw, w_pw, *, stride: int, padding: str = "SAME",
    tile_hs: Optional[Sequence[int]] = None, iters: int = 3,
    interpret: Optional[bool] = None, persist: bool = False,
    tpu: TPUConfig = TPUConfig(), residency: Optional[str] = None,
) -> Tuple[int, Tuple[Tuple[int, float], ...]]:
    """Measured fallback: time the real fused kernel per candidate tile_h.

    Returns (best_tile_h, ((tile_h, seconds_per_call), ...)).  Use when the
    analytical model ties candidates or a deployment wants ground truth; the
    sweep routes every candidate through ``telemetry.measure`` (one warmup
    call, then ``iters`` timed calls, best iteration reported), under
    ``residency`` (None = the kernels' default staging mode).  With
    ``persist=True`` the winning tile_h is recorded in the schedule cache —
    under the same residency request it was measured at — as a
    ``"measured"`` entry (which outranks model picks and, when a cache dir
    is configured, survives restarts).
    """
    from ..kernels.convdk_fused import convdk_fused_separable

    res_used = residency or DEFAULT_RESIDENCY
    out_h = -(-x.shape[1] // stride)
    if tile_hs is None:
        tile_hs = [t for t in TPUConfig().tile_h_candidates if t <= out_h] or [1]
    results = []
    for th in tile_hs:
        fn = lambda: convdk_fused_separable(  # noqa: E731
            x, w_dw, w_pw, stride=stride, padding=padding, tile_h=th,
            interpret=interpret, residency=res_used)
        m = measure(fn, iters=iters, warmup=1,
                    name=f"fused_sweep.th{th}.{res_used}")
        results.append((th, m.best_s))
    best = min(results, key=lambda r: r[1])[0]
    if persist:
        b, h, w_in, c_in = x.shape
        shape = SeparableShape(
            b=b, h=h, w=w_in, c_in=c_in, c_out=w_pw.shape[1],
            k=w_dw.shape[0], s=stride, dtype_bytes=x.dtype.itemsize)
        entry = {"tile_h": best, "source": "measured",
                 "recorded_at": time.time(),
                 "timings_s": {str(th): t for th, t in results}}
        if residency is not None:
            # only a REQUESTED residency is ground truth worth recording;
            # an unpinned sweep timed one mode's tile_h candidates without
            # comparing modes, so the auto entry leaves residency to the
            # solver (re-solved at the measured tile_h on lookup)
            entry["residency"] = res_used
        get_schedule_cache().put(
            _sep_key(shape, tpu, residency=residency), entry)
    return best, tuple(results)


def benchmark_mbconv_sweep(
    x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, *, stride: int,
    padding: str = "SAME", se_ratio: float = 0.25, iters: int = 3,
    interpret: Optional[bool] = None, persist: bool = False,
    tpu: TPUConfig = TPUConfig(),
    candidates: Optional[Sequence[dict]] = None,
) -> Tuple[dict, Tuple[dict, ...]]:
    """Measured MBConv sweep: time the real two-pass kernel per schedule
    point and let the stopwatch arbitrate the axes the byte model ties.

    ``candidates`` is a sequence of ``{"tile_h", "mode", "residency"}``
    dicts; the default set is the solver's own pick under each pinned
    pass-2 mode — the exact pair of points the retain/recompute crossover
    model claims to order, measured at the tile_h/residency each mode's
    VMEM footprint actually allows.  Returns ``(best, results)`` where
    every result dict carries the candidate axes plus ``seconds`` (best
    timed iteration via ``telemetry.measure``).  With ``persist=True``
    the winner lands in the schedule cache under the UNPINNED key as a
    ``"measured"`` entry — the tier model picks can never clobber.
    """
    from ..kernels.convdk_mbconv import convdk_mbconv_fused

    b, h, w_in, c_in = x.shape
    c_mid, c_out = w_proj.shape
    shape = MBConvShape(b=b, h=h, w=w_in, c_in=c_in, c_mid=c_mid,
                        c_out=c_out, k=w_dw.shape[0], s=stride,
                        se_ratio=se_ratio, dtype_bytes=x.dtype.itemsize)
    if candidates is None:
        candidates, seen = [], set()
        for md in MBCONV_MODES:
            pick = select_mbconv_schedule(shape, tpu, mode=md)
            point = (pick.tile_h, pick.mode, pick.residency)
            if point not in seen:
                seen.add(point)
                candidates.append({"tile_h": pick.tile_h, "mode": pick.mode,
                                   "residency": pick.residency})
    results = []
    for cand in candidates:
        th, md = int(cand["tile_h"]), cand["mode"]
        res = validate_residency(cand.get("residency") or DEFAULT_RESIDENCY)
        fn = lambda: convdk_mbconv_fused(  # noqa: E731
            x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
            stride=stride, padding=padding, tile_h=th, mode=md,
            interpret=interpret, residency=res)
        m = measure(fn, iters=iters, warmup=1,
                    name=f"mbconv_sweep.th{th}.{md}.{res}")
        results.append({"tile_h": th, "mode": md, "residency": res,
                        "seconds": m.best_s})
    best = min(results, key=lambda r: r["seconds"])
    if persist:
        entry = {"tile_h": best["tile_h"], "mode": best["mode"],
                 "residency": best["residency"], "source": "measured",
                 "recorded_at": time.time(),
                 "timings_s": {
                     f"th{r['tile_h']}.{r['mode']}.{r['residency']}":
                         r["seconds"] for r in results}}
        get_schedule_cache().put(_mbconv_key(shape, tpu), entry)
    return best, tuple(results)
