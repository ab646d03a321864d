"""MBConv / Fused-MBConv blocks and the networks built from them.

``mbconv_block`` is the model-level entry point for one mobile inverted
bottleneck with squeeze-and-excitation:

    expand 1x1 -> silu -> DW k x k / s -> silu -> SE -> project 1x1
    (+ identity residual when s == 1 and C_in == C_out)

Routing follows ``repro.configs.base.kernel_config()``: with
``kcfg.fused_mbconv`` (the default) the block runs the TWO-PASS fused
ConvDK pipeline (``kernels.convdk_mbconv_fused``) with a per-layer-shape
schedule — tile_h AND the pass-2 retain/recompute mode — solved by
``core.autotune.get_mbconv_schedule`` from the HBM traffic model.
Otherwise the staged baseline (``kernels.convdk_mbconv_staged``) runs: the
DW tensor round-trips through HBM around the SE stage.
``fusedmb_block`` is the single-pass Fused-MBConv counterpart.

``network_def`` / ``network_apply`` assemble a whole network of any
family (stem conv -> block chain -> head conv -> pool [-> fc] ->
classifier) from its config: the block specs, the stem/head activation
and the optional fc layer are data, so adding a family needs only its
spec table and config class.  ``efficientnet_b0_*``, ``mobilenet_v3_*``
and ``efficientnet_v2_s_*`` bind them to each family's default config.
The B0 stage table reproduces ``core.workloads.EFFICIENTNET_B0`` exactly
(a test asserts the consistency).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .param import P

# (expand_ratio, kernel, stride, c_out, repeats) — EfficientNet-B0 stages
# 2-8 [arXiv:1905.11946, Table 1]; the first block of a stage carries the
# stride, channel changes happen on that block, SE ratio 0.25 throughout.
EFFNET_B0_STAGES: Tuple[Tuple[int, int, int, int, int], ...] = (
    (1, 3, 1, 16, 1),
    (6, 3, 2, 24, 2),
    (6, 5, 2, 40, 2),
    (6, 3, 2, 80, 3),
    (6, 5, 1, 112, 3),
    (6, 5, 2, 192, 4),
    (6, 3, 1, 320, 1),
)


@dataclasses.dataclass(frozen=True)
class EffNetConfig:
    """EfficientNet-family hyperparameters (B0 defaults).

    ``width_mult`` scales every channel count through ``round_filters``
    (divisor-8 rounding, the paper's compound-scaling rule) — small
    multipliers give CI-sized models with the exact B0 topology.
    """

    num_classes: int = 1000
    width_mult: float = 1.0
    se_ratio: float = 0.25
    stem_c: int = 32
    head_c: int = 1280
    stages: Tuple[Tuple[int, int, int, int, int], ...] = EFFNET_B0_STAGES
    dtype: str = "float32"
    # stem and head activation, a ``jax.nn`` name (class data, not a field)
    stem_head_act: ClassVar[str] = "silu"

    def block_specs(self) -> List["MBConvSpec"]:
        return effnet_block_specs(self)


def round_filters(c: int, width_mult: float, divisor: int = 8) -> int:
    """EfficientNet channel rounding: scale, snap to the divisor, never
    drop below 90 % of the scaled value."""
    if width_mult == 1.0:
        return c
    c_scaled = c * width_mult
    new_c = max(divisor, int(c_scaled + divisor / 2) // divisor * divisor)
    if new_c < 0.9 * c_scaled:
        new_c += divisor
    return int(new_c)


@dataclasses.dataclass(frozen=True)
class MBConvSpec:
    """One resolved block instance inside a network.

    The block FAMILY is data on the spec (``"mbconv"`` — the two-pass
    SE-aware pipeline — or ``"fusedmb"`` — EfficientNet-V2's single-pass
    dense-conv + projection collapse), as are the per-block activation
    and SE facts MobileNet-V3 varies stage by stage: ``act`` is the main
    activation (expand/DW for MBConv, the dense conv for Fused-MBConv),
    ``se_ratio <= 0`` means NO squeeze-excite (the kernels then skip the
    pool/gate entirely), and ``se_act``/``gate_act`` are the SE-internal
    nonlinearities ((silu, sigmoid) for EfficientNet, (relu,
    hard_sigmoid) for V3).  ``c_mid_override`` pins the expanded width
    directly for tables whose expansion is not an integer multiple of
    ``c_in`` (most of MobileNet-V3)."""

    c_in: int
    c_out: int
    expand_ratio: int
    k: int
    s: int
    se_ratio: float = 0.25
    c_mid_override: Optional[int] = None
    act: str = "silu"
    se_act: str = "silu"
    gate_act: str = "sigmoid"
    family: str = "mbconv"

    def __post_init__(self):
        from ..configs.base import BLOCK_FAMILIES
        if self.family not in ("mbconv", "fusedmb"):
            raise ValueError(
                f"MBConvSpec.family must be 'mbconv' or 'fusedmb' "
                f"(of {BLOCK_FAMILIES}), got {self.family!r}")
        if self.family == "fusedmb" and self.se_ratio > 0:
            # the fusedmb family never carries SE — normalize, mirroring
            # core.autotune.BlockRow
            object.__setattr__(self, "se_ratio", 0.0)

    @property
    def c_mid(self) -> int:
        if self.c_mid_override is not None:
            return self.c_mid_override
        return self.c_in * self.expand_ratio

    @property
    def has_se(self) -> bool:
        return self.family == "mbconv" and self.se_ratio > 0

    @property
    def c_se(self) -> int:
        if not self.has_se:
            return 0
        return max(1, int(self.c_in * self.se_ratio))

    @property
    def has_residual(self) -> bool:
        return self.s == 1 and self.c_in == self.c_out


def effnet_block_specs(cfg: EffNetConfig) -> List[MBConvSpec]:
    """The per-block MBConv table of one EfficientNet config."""
    return _stage_specs(cfg, [("mbconv",) + st for st in cfg.stages])


def _stage_specs(cfg, stages) -> List[MBConvSpec]:
    """Specs of EfficientNet (family, expand_ratio, k, s, c_out, repeats)
    stages: the first block of a stage carries the stride; ``fusedmb``
    blocks never carry SE, and at expansion 1 their conv goes straight
    to c_out (no projection, so c_mid = c_out); ``mbconv`` blocks
    expand c_in by the ratio and carry SE at ``cfg.se_ratio``."""
    specs: List[MBConvSpec] = []
    c_in = round_filters(cfg.stem_c, cfg.width_mult)
    for family, expand, k, s, c_out, repeats in stages:
        c_out = round_filters(c_out, cfg.width_mult)
        for i in range(repeats):
            c_mid = None
            if family == "fusedmb":
                c_mid = c_out if expand == 1 else c_in * expand
            specs.append(MBConvSpec(
                c_in=c_in, c_out=c_out, expand_ratio=expand, k=k,
                s=s if i == 0 else 1,
                se_ratio=0.0 if family == "fusedmb" else cfg.se_ratio,
                c_mid_override=c_mid, family=family))
            c_in = c_out
    return specs


def block_chain_rows(specs: List[MBConvSpec], h: int, w: int) -> tuple:
    """``core.autotune.BlockRow`` chain rows for the network-level layout
    solver, threading the spatial dims through each block's stride from
    the STEM-OUTPUT dims ``h`` x ``w``; each row carries its spec's
    family, act and SE ratio.  Shared by ``network_apply`` and the vision
    serving engine, so both price the same chain."""
    from ..core.autotune import BlockRow
    rows, hh, ww = [], h, w
    for sp in specs:
        rows.append(BlockRow(hh, ww, sp.c_in, sp.c_mid, sp.c_out, sp.k,
                             sp.s, family=sp.family, act=sp.act,
                             se_ratio=sp.se_ratio))
        hh, ww = -(-hh // sp.s), -(-ww // sp.s)
    return tuple(rows)


# ---------------------------------------------------------------------------
# one MBConv block
# ---------------------------------------------------------------------------

def mbconv_def(c_in: int, c_out: int, k: int = 3, expand_ratio: int = 6,
               se_ratio: float = 0.25, c_mid: Optional[int] = None) -> dict:
    """Params of one MBConv block.  Convs are bias-free (BN would own the
    bias); the SE FCs carry biases, as in the reference EfficientNet.
    ``se_ratio <= 0`` omits the SE FCs entirely (the param tree IS the
    se=off contract: ``mbconv_block`` passes ``None`` SE weights to the
    kernels when the keys are absent).  ``c_mid`` pins a non-integer
    expansion width directly (MobileNet-V3 tables)."""
    spec = MBConvSpec(c_in=c_in, c_out=c_out, expand_ratio=expand_ratio,
                      k=k, s=1, se_ratio=se_ratio, c_mid_override=c_mid)
    c_mid, c_se = spec.c_mid, spec.c_se
    p: Dict[str, Any] = {
        "dw": P((k, k, c_mid), (None, None, None)),
        "proj": P((c_mid, c_out), (None, None), scale=2.0),
    }
    if spec.has_se:
        p["se_w1"] = P((c_mid, c_se), (None, None), scale=2.0)
        p["se_b1"] = P((c_se,), (None,), init="zeros")
        p["se_w2"] = P((c_se, c_mid), (None, None), scale=2.0)
        p["se_b2"] = P((c_mid,), (None,), init="zeros")
    if c_mid != c_in:
        p["exp"] = P((c_in, c_mid), (None, None), scale=2.0)
    return p


def fusedmb_def(c_in: int, c_out: int, c_mid: int, k: int = 3,
                expand_ratio: Optional[int] = None) -> dict:
    """Params of one Fused-MBConv block: the dense k x k conv that
    collapses expand+DW (HWIO), plus the 1x1 projection — except at
    expansion ratio 1, where the published block is the k x k conv
    alone, straight to ``c_out`` (``c_mid`` must equal it): no ``proj``.
    """
    p: Dict[str, Any] = {"conv": P((k, k, c_in, c_mid), (None,) * 4)}
    if expand_ratio == 1:
        assert c_mid == c_out, (c_mid, c_out)
    else:
        p["proj"] = P((c_mid, c_out), (None, None), scale=2.0)
    return p


def block_def(sp: MBConvSpec) -> dict:
    """Family dispatch: the param tree of one spec'd block."""
    if sp.family == "fusedmb":
        return fusedmb_def(sp.c_in, sp.c_out, sp.c_mid, k=sp.k,
                           expand_ratio=sp.expand_ratio)
    return mbconv_def(sp.c_in, sp.c_out, k=sp.k,
                      expand_ratio=sp.expand_ratio, se_ratio=sp.se_ratio,
                      c_mid=sp.c_mid_override)


def mbconv_block(
    x,
    params=None,
    *,
    stride: int = 1,
    padding: str = "SAME",
    exp_act: Optional[str] = "silu",
    dw_act: Optional[str] = "silu",
    se_act: Optional[str] = "silu",
    gate_act: Optional[str] = "sigmoid",
    cfg=None,
    mesh=None,
    pin=None,
    in_layout: str = "replicated",
    overlap: Optional[str] = None,
    kcfg=None,
):
    """Apply one MBConv block, routed by the conv-kernel config.

    Canonical signature: ``mbconv_block(x, params, *, cfg, mesh, pin,
    in_layout)`` returning ``(y, out_layout)`` — symmetric with
    ``separable_block``, so the network-level layout solver can thread a
    block chain through either family.  The legacy positional order
    (``params`` first, bare-array return) and the ``kcfg=`` kwarg keep
    working behind a warn-once deprecation shim.

    With ``fused`` (the default) the block runs the two-pass fused ConvDK
    pipeline: pass 1 fuses expand-PW + DW per strip and accumulates the
    SE pool on-chip; pass 2 folds the SE gate into the projection in the
    same VMEM residency.  The per-layer (tile_h, mode, residency)
    schedule — residency being the strip-staging mode of
    ``kernels.staging`` — comes from ``core.autotune.get_mbconv_schedule``
    unless ``pin`` (or the legacy config fields) pins one.  The identity
    residual is added when the shapes allow (s == 1, C_in == C_out).

    With a ``mesh`` (and the shard toggle), the fused pipeline runs
    mesh-sharded via ``shard_map``: batch on "data" (jointly with a "pod"
    axis when present), the expanded c_mid grid on "model", the SE pool
    psum'd across the model axis
    (``kernels.convdk_mbconv_fused_sharded``) — falling back to the
    single-device kernel when the mesh axes do not divide the grid.  The
    (tile_h, mode, residency, collective) schedule is then solved per
    (partitioning, layout); when the solver picks ``psum_scatter`` the
    block output comes back sharded on c_out (identical values) and
    ``out_layout`` reports ``"model_sharded"``.

    ``in_layout`` declares the ARRIVAL layout: ``"model_sharded"``
    (c_in sharded on "model", dividing) is consumed collective-free by
    identity-expand blocks (the only place it strictly wins — the
    network DP exploits exactly this) and via an entry all-gather by
    real-expand blocks (byte-identical to a boundary regather: the dense
    expand needs all of c_in, which is why e > 1 boundaries tie).

    ``overlap`` declares the ENTRY-boundary overlap mode the caller's
    chain executor runs this block under ("serial" | "pipelined", see
    ``core.perfmodel.OVERLAP_MODES``; None = serial).  It does not change
    the block's math — it threads into the schedule lookup so a
    pipelined entry solves under the halved pass-1 VMEM budget (two
    blocks share VMEM while their stages overlap) and caches under its
    own ``ov=`` key segment.

    x: (B, H, W, C_in) NHWC -> (B, H', W', C_out); an ``x`` in the lane
    layout the network's chain carries (``kernels.common.in_lane_layout``:
    C_in rounded up to whole 128-lane groups, zero tail) gives an output
    in it.  C_in is read from the params.
    """
    from ..configs.base import _warn_once, kernel_config, resolve_pin
    legacy_call = isinstance(x, dict)
    if legacy_call:
        _warn_once(
            "mbconv_block_positional",
            "mbconv_block(params, x) is deprecated; call "
            "mbconv_block(x, params, ...) — the new order returns "
            "(y, out_layout)")
        x, params = params, x
    if kcfg is not None:
        _warn_once(
            "block_kcfg_kwarg",
            "the kcfg= kwarg on block entries is deprecated; pass cfg=")
        if cfg is None:
            cfg = kcfg
    if cfg is None:
        cfg = kernel_config()
    from ..core.perfmodel import validate_layout
    from ..kernels import (
        can_shard_fused, conv_mesh_shape, convdk_mbconv_fused,
        convdk_mbconv_fused_sharded, convdk_mbconv_staged,
    )

    validate_layout(in_layout)
    eff = resolve_pin(cfg, pin, family="mbconv")
    c_mid = params["dw"].shape[-1]
    c_in = params["exp"].shape[0] if "exp" in params else c_mid
    c_out = params["proj"].shape[-1]
    # the param tree IS the SE contract: absent SE FCs mean a no-SE block
    # (MobileNet-V3's early/middle stages) — the kernels then skip the
    # pass-1 pool, the host MLP and the pass-2 gate entirely
    has_se = "se_w1" in params
    if eff.se == "on" and not has_se:
        raise ValueError("se='on' pinned on a block whose params carry "
                         "no SE FCs")
    if "exp" in params:
        w_exp = params["exp"].astype(x.dtype)
        eff_exp_act = exp_act
    else:
        # expansion ratio 1 (MBConv1): no expand convolution and no expand
        # activation — the kernels' taps read the block input directly
        w_exp, eff_exp_act = None, None

    sharded = (mesh is not None and eff.shard and eff.fused
               and can_shard_fused(mesh, x.shape[0], c_mid))
    mesh_shape = conv_mesh_shape(mesh) if sharded else (1, 1)
    # a sharded arrival additionally needs c_in to divide the model factor
    eff_in_layout = ("model_sharded"
                     if (sharded and in_layout == "model_sharded"
                         and c_in % mesh_shape[1] == 0)
                     else "replicated")
    pinned_collective = eff.resolved_collective
    tile_h, mode = cfg.tile_h, eff.mode or "retain"
    residency = eff.residency
    collective = pinned_collective
    if cfg.autotune:
        from ..core.autotune import (
            ACT_MODES, DEFAULT_ACT, get_mbconv_schedule,
        )
        from ..core.perfmodel import DEFAULT_OVERLAP
        b, h, w, _ = x.shape
        se_ratio = (params["se_w1"].shape[1] / max(1, c_in)) if has_se \
            else 0.0
        sched_act = dw_act if dw_act in ACT_MODES else DEFAULT_ACT
        # a pinned mbconv_mode enters the solve: tile_h/residency must be
        # VMEM-feasible under THAT mode's footprint, not the free winner's
        sch = get_mbconv_schedule(
            b, h, w, c_in, c_mid, c_out, params["dw"].shape[0], stride,
            se_ratio=se_ratio, dtype_bytes=x.dtype.itemsize,
            mesh_shape=mesh_shape, residency=eff.residency,
            mode=eff.mode, collective=pinned_collective,
            in_layout=eff_in_layout,
            overlap=overlap if overlap is not None else DEFAULT_OVERLAP,
            act=sched_act)
        tile_h = sch.tile_h
        mode = sch.mode
        residency = sch.residency
        collective = sch.collective

    args = (x, w_exp, params["dw"].astype(x.dtype),
            params.get("se_w1"), params.get("se_b1"), params.get("se_w2"),
            params.get("se_b2"), params["proj"].astype(x.dtype))
    if sharded:
        out = convdk_mbconv_fused_sharded(
            *args, mesh=mesh, stride=stride, padding=padding, tile_h=tile_h,
            mode=mode, exp_act=eff_exp_act, dw_act=dw_act,
            se_act=se_act, gate_act=gate_act,
            interpret=cfg.interpret, residency=residency,
            collective=collective, in_layout=eff_in_layout)
        # a padded scatter (non-dividing c_out) comes back sliced — not
        # cleanly shard-consumable, so it reports replicated
        out_layout = ("model_sharded"
                      if (collective == "psum_scatter"
                          and c_out % mesh_shape[1] == 0)
                      else "replicated")
    elif eff.fused:
        out = convdk_mbconv_fused(
            *args, stride=stride, padding=padding, tile_h=tile_h, mode=mode,
            exp_act=eff_exp_act, dw_act=dw_act, se_act=se_act,
            gate_act=gate_act, interpret=cfg.interpret,
            residency=residency)
        out_layout = "replicated"
    else:
        out = convdk_mbconv_staged(
            *args, stride=stride, padding=padding, tile_h=tile_h,
            exp_act=eff_exp_act, dw_act=dw_act, se_act=se_act,
            gate_act=gate_act, interpret=cfg.interpret)
        out_layout = "replicated"
    if stride == 1 and c_in == c_out and out.shape == x.shape:
        out = out + x
    if legacy_call:
        return out
    return out, out_layout


# ---------------------------------------------------------------------------
# one Fused-MBConv block
# ---------------------------------------------------------------------------

def fusedmb_block(
    x,
    params,
    *,
    stride: int = 1,
    padding: str = "SAME",
    act: Optional[str] = "silu",
    cfg=None,
    mesh=None,
    pin=None,
    in_layout: str = "replicated",
    overlap: Optional[str] = None,
):
    """Apply one Fused-MBConv block (EfficientNet-V2's fused stages),
    routed by the conv-kernel config — returns ``(y, out_layout)``,
    symmetric with ``mbconv_block``/``separable_block`` so the
    network-level layout solver threads mixed-family chains through one
    executor.

    With ``fused`` (the default) the whole block runs as the SINGLE-PASS
    ``kernels.convdk_fusedmb_fused`` pipeline: dense k x k conv
    (collapsed expand+DW), activation and the 1x1 projection in one VMEM
    residency — the expanded (C_mid) tensor never touches HBM, there is
    no SE stage and no second pass.  The (tile_h, residency, collective)
    schedule comes from ``core.autotune.get_fusedmb_schedule``.

    The family consumes REPLICATED arrivals only (the dense conv needs
    all of c_in): ``in_layout="model_sharded"`` raises, mirroring the
    kernel and perfmodel contracts — the network DP never proposes it.
    Under a mesh the expanded c_mid grid shards on "model" and the
    projection reduction crosses devices per the solved collective; a
    ``psum_scatter`` exit on a dividing c_out reports
    ``out_layout="model_sharded"``, as does a sharded block with no
    projection.  The identity residual is added when the shapes allow
    (s == 1, C_in == C_out).

    Params without ``"proj"`` are the expansion-1 block, which has no
    projection: the kernels get ``w_proj=None`` and compute act(conv).

    x: (B, H, W, C_in) NHWC -> (B, H', W', C_out), or both in the lane
    layout, as for ``mbconv_block``.
    """
    from ..configs.base import kernel_config, resolve_pin
    if cfg is None:
        cfg = kernel_config()
    from ..core.perfmodel import validate_layout
    from ..kernels import (
        can_shard_fused, conv_mesh_shape, convdk_fusedmb_fused,
        convdk_fusedmb_fused_sharded, convdk_fusedmb_staged,
    )

    validate_layout(in_layout)
    if in_layout == "model_sharded":
        raise ValueError(
            "fusedmb consumes replicated arrivals only, got "
            f"{in_layout!r}")
    eff = resolve_pin(cfg, pin, family="fusedmb")
    w_conv = params["conv"].astype(x.dtype)
    w_proj = params["proj"].astype(x.dtype) if "proj" in params else None
    c_in = w_conv.shape[2]
    c_mid = w_conv.shape[-1]
    c_out = c_mid if w_proj is None else w_proj.shape[-1]
    k = w_conv.shape[0]

    sharded = (mesh is not None and eff.shard and eff.fused
               and can_shard_fused(mesh, x.shape[0], c_mid))
    mesh_shape = conv_mesh_shape(mesh) if sharded else (1, 1)
    collective = eff.resolved_collective
    tile_h, residency = cfg.tile_h, eff.residency
    if cfg.autotune:
        from ..core.autotune import (
            ACT_MODES, DEFAULT_ACT, get_fusedmb_schedule,
        )
        from ..core.perfmodel import DEFAULT_OVERLAP
        b, h, w, _ = x.shape
        sched_act = act if act in ACT_MODES else DEFAULT_ACT
        sch = get_fusedmb_schedule(
            b, h, w, c_in, c_mid, c_out, k, stride,
            dtype_bytes=x.dtype.itemsize, mesh_shape=mesh_shape,
            residency=eff.residency, collective=collective,
            overlap=overlap if overlap is not None else DEFAULT_OVERLAP,
            act=sched_act)
        tile_h = sch.tile_h
        residency = sch.residency
        collective = sch.collective

    if sharded:
        out = convdk_fusedmb_fused_sharded(
            x, w_conv, w_proj, mesh=mesh, stride=stride, padding=padding,
            tile_h=tile_h, act=act, interpret=cfg.interpret,
            residency=residency, collective=collective,
            in_layout="replicated")
        out_layout = ("model_sharded"
                      if w_proj is None or (collective == "psum_scatter"
                                            and c_out % mesh_shape[1] == 0)
                      else "replicated")
    elif eff.fused:
        out = convdk_fusedmb_fused(
            x, w_conv, w_proj, stride=stride, padding=padding,
            tile_h=tile_h, act=act, interpret=cfg.interpret,
            residency=residency)
        out_layout = "replicated"
    else:
        out = convdk_fusedmb_staged(
            x, w_conv, w_proj, stride=stride, padding=padding,
            tile_h=tile_h, act=act, interpret=cfg.interpret)
        out_layout = "replicated"
    if stride == 1 and c_in == c_out and out.shape == x.shape:
        out = out + x
    return out, out_layout


# ---------------------------------------------------------------------------
# MobileNet-V3-Large
# ---------------------------------------------------------------------------

# (c_mid, c_out, k, s, SE, act) per block — MobileNet-V3-Large
# [arXiv:1905.02244, Table 1]; c_in threads from the previous block (stem
# 16).  The expanded widths are NOT integer multiples of c_in (72 = 3 x
# 24 but 200 = 2.5 x 80), so the specs pin c_mid directly.  The DW stage
# of every row reproduces core.workloads.MOBILENET_V3_LARGE (a test pins
# the two views together).
MOBILENET_V3_LARGE_BLOCKS: Tuple[
        Tuple[int, int, int, int, bool, str], ...] = (
    (16, 16, 3, 1, False, "relu"),
    (64, 24, 3, 2, False, "relu"),
    (72, 24, 3, 1, False, "relu"),
    (72, 40, 5, 2, True, "relu"),
    (120, 40, 5, 1, True, "relu"),
    (120, 40, 5, 1, True, "relu"),
    (240, 80, 3, 2, False, "hard_swish"),
    (200, 80, 3, 1, False, "hard_swish"),
    (184, 80, 3, 1, False, "hard_swish"),
    (184, 80, 3, 1, False, "hard_swish"),
    (480, 112, 3, 1, True, "hard_swish"),
    (672, 112, 3, 1, True, "hard_swish"),
    (672, 160, 5, 2, True, "hard_swish"),
    (960, 160, 5, 1, True, "hard_swish"),
    (960, 160, 5, 1, True, "hard_swish"),
)


@dataclasses.dataclass(frozen=True)
class MobileNetV3Config:
    """MobileNet-V3-Large hyperparameters.  ``width_mult`` scales every
    channel count (including the pinned expanded widths) through
    ``round_filters`` — small multipliers give CI-sized models with the
    exact V3-Large topology, SE placement and act mix."""

    num_classes: int = 1000
    width_mult: float = 1.0
    se_ratio: float = 0.25
    stem_c: int = 16
    head_c: int = 960
    cls_c: int = 1280
    blocks: Tuple[Tuple[int, int, int, int, bool, str], ...] = \
        MOBILENET_V3_LARGE_BLOCKS
    dtype: str = "float32"
    stem_head_act: ClassVar[str] = "hard_swish"

    def block_specs(self) -> List[MBConvSpec]:
        return mobilenet_v3_specs(self)


def mobilenet_v3_specs(cfg: MobileNetV3Config) -> List[MBConvSpec]:
    """The per-block spec table of one MobileNet-V3 config: per-block
    act, SE-on-some-blocks (se_ratio 0 elsewhere), and the V3 SE flavor
    (relu squeeze, hard_sigmoid gate)."""
    specs: List[MBConvSpec] = []
    c_in = round_filters(cfg.stem_c, cfg.width_mult)
    for c_mid, c_out, k, s, se, act in cfg.blocks:
        c_mid = round_filters(c_mid, cfg.width_mult)
        c_out = round_filters(c_out, cfg.width_mult)
        specs.append(MBConvSpec(
            c_in=c_in, c_out=c_out, expand_ratio=1, k=k, s=s,
            se_ratio=cfg.se_ratio if se else 0.0, c_mid_override=c_mid,
            act=act, se_act="relu", gate_act="hard_sigmoid"))
        c_in = c_out
    return specs


# ---------------------------------------------------------------------------
# EfficientNet-V2-S
# ---------------------------------------------------------------------------

# (family, expand_ratio, k, s, c_out, repeats) — EfficientNet-V2-S body
# [arXiv:2104.00298, Table 2]: Fused-MBConv stages 1-3 (the dense
# expand+DW collapse, no SE), MBConv tail with SE 0.25.  The first block
# of a stage carries the stride.
EFFNET_V2_S_STAGES: Tuple[Tuple[str, int, int, int, int, int], ...] = (
    ("fusedmb", 1, 3, 1, 24, 2),
    ("fusedmb", 4, 3, 2, 48, 4),
    ("fusedmb", 4, 3, 2, 64, 4),
    ("mbconv", 4, 3, 2, 128, 6),
    ("mbconv", 6, 3, 1, 160, 9),
    ("mbconv", 6, 3, 2, 256, 15),
)


@dataclasses.dataclass(frozen=True)
class EffNetV2Config:
    """EfficientNet-V2-S hyperparameters (same ``width_mult`` scaling
    rule as ``EffNetConfig``; shrink ``stages`` for CI-sized chains that
    keep the fused-head + MBConv-tail mix)."""

    num_classes: int = 1000
    width_mult: float = 1.0
    se_ratio: float = 0.25
    stem_c: int = 24
    head_c: int = 1280
    stages: Tuple[Tuple[str, int, int, int, int, int], ...] = \
        EFFNET_V2_S_STAGES
    dtype: str = "float32"
    stem_head_act: ClassVar[str] = "silu"

    def block_specs(self) -> List[MBConvSpec]:
        return effnet_v2_block_specs(self)


def effnet_v2_block_specs(cfg: EffNetV2Config) -> List[MBConvSpec]:
    """The per-block spec table of one EfficientNet-V2 config."""
    return _stage_specs(cfg, cfg.stages)


# ---------------------------------------------------------------------------
# the network: stem -> block chain -> head, for every family
# ---------------------------------------------------------------------------

STEM_STRIDE = 2      # the stem conv halves the spatial dims


def network_def(cfg) -> dict:
    """Param tree: stem conv -> blocks -> head conv [-> fc] -> classifier.

    Every block comes from ``block_def`` of ``cfg.block_specs()``; a
    config with a ``cls_c`` (MobileNet-V3) gets the dense ``fc`` layer
    between the pooled head and the classifier."""
    specs = cfg.block_specs()
    stem_c = round_filters(cfg.stem_c, cfg.width_mult)
    head_c = round_filters(cfg.head_c, cfg.width_mult)
    p: Dict[str, Any] = {
        "stem": P((3, 3, 3, stem_c), (None,) * 4),
        "head": P((specs[-1].c_out, head_c), (None, None), scale=2.0),
    }
    cls_in = head_c
    if getattr(cfg, "cls_c", None) is not None:
        cls_in = round_filters(cfg.cls_c, cfg.width_mult)
        p["fc"] = P((head_c, cls_in), (None, None), scale=2.0)
    p["cls_w"] = P((cls_in, cfg.num_classes), (None, None))
    p["cls_b"] = P((cfg.num_classes,), (None,), init="zeros")
    for i, sp in enumerate(specs):
        p[f"block{i}"] = block_def(sp)
    return p


def network_apply(params: dict, images: jax.Array, cfg, kcfg=None,
                  mesh=None, plan=None) -> jax.Array:
    """(B, H, W, 3) images -> (B, num_classes) logits, for any family.

    The family is data: ``cfg.block_specs()`` is the block chain (each
    spec's family, act and SE facts route its block), ``cfg.stem_head_act``
    names the stem and head activation, and an ``"fc"`` entry in
    ``params`` adds a dense layer (same activation) after the pool.

    Without a ``plan`` and with a ``mesh``, the per-block schedules come
    from the network-level layout solve (``core.autotune.get_network_plan``
    over ``block_chain_rows``), which may materialize the stem output
    model-sharded; ``plan`` passes a pre-solved ``NetworkPlan`` instead
    (the vision serving engine solves one per bucket, so the bytes its
    counters charge are the schedules that run).  The chain lowers
    through ``models.blockgraph``: ``validate()`` proves every pipelined
    boundary hazard-free and ``lower()`` runs each block under its
    ``mbconv<i>`` / ``fusedmb<i>`` name scope.

    Between the stem and the head every activation is in the kernels'
    lane layout (``kernels.common.in_lane_layout``): each block's kernels
    stage it as it is, with no padded copy in front of a launch."""
    from ..kernels.common import to_lanes
    specs = cfg.block_specs()
    act = getattr(jax.nn, cfg.stem_head_act)
    dt = jnp.dtype(cfg.dtype)
    if kcfg is None:
        from ..configs.base import kernel_config
        kcfg = kernel_config()
    with jax.named_scope("stem"):
        # zero filters give the stem output the kernels' lane layout,
        # which the chain carries from here to the head: act(0) = 0 for
        # every stem activation, and no XLA op pads the activation
        x = act(jax.lax.conv_general_dilated(
            images.astype(dt), to_lanes(params["stem"].astype(dt)),
            (STEM_STRIDE, STEM_STRIDE), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        if plan is None and (mesh is not None and kcfg.shard_fused
                             and kcfg.fused_mbconv and kcfg.autotune):
            from ..core.autotune import get_network_plan
            from ..kernels import conv_mesh_shape
            b, h, w, _c0 = x.shape
            plan = get_network_plan(block_chain_rows(specs, h, w), b,
                                    conv_mesh_shape(mesh),
                                    dtype_bytes=dt.itemsize,
                                    se_ratio=cfg.se_ratio)
        if (plan is not None and mesh is not None
                and plan.stem_layout == "model_sharded"):
            # materialize the stem output once per element mesh-wide: each
            # device of a model group holds only its c0/mp channel slice,
            # which block0's sharded-in entry consumes without a gather
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P
            from ..kernels.convdk_sharded import MODEL_AXIS, _batch_axes
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, _P(_batch_axes(mesh), None, None,
                                          MODEL_AXIS)))

    from .blockgraph import build_block_graph
    graph = build_block_graph(specs, params, kcfg=kcfg, mesh=mesh,
                              plan=plan)
    graph.validate()
    x = graph.lower(x)
    with jax.named_scope("head"):
        # zero rows for the lane tail the last block's output carries
        w_head = params["head"].astype(x.dtype)
        w_head = jnp.pad(w_head, ((0, x.shape[-1] - w_head.shape[0]),
                                  (0, 0)))
        x = act(jnp.einsum("bhwc,cd->bhwd", x, w_head))
        x = x.mean(axis=(1, 2))
        if "fc" in params:
            x = act(x @ params["fc"].astype(x.dtype))
        return (x @ params["cls_w"].astype(x.dtype)
                + params["cls_b"].astype(x.dtype))


# the families' named entries: the network at each default config

def efficientnet_b0_def(cfg: EffNetConfig = EffNetConfig()) -> dict:
    return network_def(cfg)


def efficientnet_b0_apply(params: dict, images: jax.Array,
                          cfg: EffNetConfig = EffNetConfig(),
                          kcfg=None, mesh=None, plan=None) -> jax.Array:
    return network_apply(params, images, cfg, kcfg, mesh=mesh, plan=plan)


def mobilenet_v3_def(cfg: MobileNetV3Config = MobileNetV3Config()) -> dict:
    return network_def(cfg)


def mobilenet_v3_apply(params: dict, images: jax.Array,
                       cfg: MobileNetV3Config = MobileNetV3Config(),
                       kcfg=None, mesh=None, plan=None) -> jax.Array:
    return network_apply(params, images, cfg, kcfg, mesh=mesh, plan=plan)


def efficientnet_v2_s_def(cfg: EffNetV2Config = EffNetV2Config()) -> dict:
    return network_def(cfg)


def efficientnet_v2_s_apply(params: dict, images: jax.Array,
                            cfg: EffNetV2Config = EffNetV2Config(),
                            kcfg=None, mesh=None, plan=None) -> jax.Array:
    return network_apply(params, images, cfg, kcfg, mesh=mesh, plan=plan)
