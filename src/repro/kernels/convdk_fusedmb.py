"""Single-pass Fused-MBConv (EfficientNet-V2) ConvDK Pallas kernel.

EfficientNet-V2's early stages replace MBConv's expand-PW + depthwise pair
with ONE dense k x k convolution (``Fused-MBConv``):

    dense k x k / s conv (C_in -> C_mid) -> act -> project 1x1 (+ residual)

At expansion ratio 1 the published block has no projection: the k x k
conv goes straight to C_out and the block is act(conv) (+ residual).
``w_proj=None`` launches that form: no c_mid grid dimension, no
projection weight and no projection accumulator; a square ``w_proj`` a
caller does pass still runs as a projection.

There is no SE stage, and therefore no global pool coupling distant strips:
the projection of a strip depends only on that strip's conv output.  That
is exactly the locality the single-strip VMEM residency of
``convdk_fused_separable`` exploits — so unlike MBConv (which needs the
two-pass schedule of ``convdk_mbconv``), Fused-MBConv fuses in **one
pass**: per (c_out block, row strip), the dense conv accumulates over the
c_in blocks of the staged halo'd input window, the activation applies in
VMEM, and the projection contracts over the c_mid blocks — the expanded
tensor NEVER exists in HBM.

Grid layout mirrors MBConv's recompute pass 2: ``(batch, c_out_block,
row_strip, c_mid_block, c_in_block)`` with c_in innermost (the dense-conv
reduction) and c_mid next (the projection reduction).  The input stream
stages through the shared strip engine (``kernels.staging``) under the
schedule's **residency** axis — identical windows to an MBConv pass-1
stream, re-read once per (c_out, c_mid) block pair.  Without a
projection the grid is ``(batch, c_mid_block, row_strip, c_in_block)``.

A narrow input packs its taps into the contraction.  The channel block
is 128 lanes, so a dense conv over c_in = 24 contracts 104 zero lanes per
tap.  When c_in fits at least twice in the block, ``taps_per_pass``
gives T = floor(128 / c_in): the kernel rolls T taps' window slices into
disjoint lane ranges of one (rows, 128) patch (the staged window's lanes
from c_in up are zero, so a roll by t * c_in and an add place tap t
exactly) and contracts the patch once against a packed weight plane —
ceil(k*k / T) MXU passes per (c_mid block, strip) instead of k*k.  T = 1
(c_in over 64, or over several blocks) is the plain per-tap loop.

Because the whole block is one pass, its schedule has NO mode axis (there
is no DW tensor to retain or recompute) and its **pass-2 figures are
exactly zero** by convention: ``core.perfmodel.fusedmb_pass_traffic``
prices the entire block as pass 1.  A pipelined network boundary cannot
hide a predecessor's pass-1 DMA behind this block's (empty) pass 2 —
``core.autotune._annotate_overlap`` keeps such boundaries serial.

The sharded wrapper (``convdk_sharded``) puts c_mid on "model" like
MBConv: conv partials are channel-local (every device holds ALL of c_in —
a dense conv cannot consume a c_in-sharded arrival), the projection
reduces over c_mid per the schedule's collective (psum / psum_scatter).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import telemetry
from ..core.perfmodel import (
    DEFAULT_COLLECTIVE,
    DEFAULT_RESIDENCY,
    LANES,
    pick_channel_block,
    validate_collective,
)
from .common import (
    LaunchGeometry,
    as_layout,
    compiler_params,
    default_interpret,
    in_lane_layout,
    launch_geometry,
    round_up as _round_up,
    stages_in_place,
    store_out,
    strip_source,
    to_lanes,
)
from .ref import _act_ref, fusedmb_ref
from .staging import StripPlan, StripStream, strip_plan


def taps_per_pass(c_in: int, n_taps: int) -> int:
    """Conv taps one MXU contraction packs side by side in a 128-lane
    channel block: floor(128 / c_in), at most all ``n_taps``; 1 when c_in
    is over 64 (it fits the block once, or spans several blocks)."""
    return max(1, min(n_taps, LANES // c_in))


def pack_conv_taps(w_conv, ci_pad: int, cm_pad: int):
    """(k_h, k_w, c_in, c_mid) HWIO -> (n_groups, ci_pad, cm_pad): the
    weight planes of the ``taps_per_pass`` groups.  Row t * c_in + c of
    group g holds channel c of tap g * T + t (taps in row-major order);
    every other row is zero.  At T = 1 this is the taps' planes, padded."""
    k_h, k_w, c_in, c_mid = w_conv.shape
    n_taps = k_h * k_w
    t = taps_per_pass(c_in, n_taps)
    n_groups = -(-n_taps // t)
    w = jnp.pad(w_conv.reshape(n_taps, c_in, c_mid),
                ((0, n_groups * t - n_taps), (0, 0), (0, cm_pad - c_mid)))
    w = w.reshape(n_groups, t * c_in, cm_pad)
    return jnp.pad(w, ((0, 0), (0, ci_pad - t * c_in), (0, 0)))


def _fusedmb_kernel(x_ref, wconv_ref, *refs, plan: StripPlan, project: bool,
                    k_h, k_w, c_in, stride, tile_h, out_w,
                    act: Optional[str]):
    """One (batch, c_out-block, row-strip[, c_mid-block], c_in-block) cell
    (the c_mid dim only when ``project``).

    x_ref     : unstaged input (engine-staged per ``plan``)
    wconv_ref : (n_groups, CI, CM)    the dense conv's packed tap planes
                (``pack_conv_taps``); ``c_in`` is the real input width
    then      : wproj_ref (CM, CO), the projection block, only when
                ``project``; o_ref (1, tile_h, out_w, CO); the conv
                accumulator (tile_h, out_w, CM) f32 carrying partial
                dense-conv sums across the c_in grid dim; when
                ``project``, the projection accumulator (tile_h, out_w,
                CO) f32 carrying partial sums across the c_mid grid dim;
                (o_ref's block holds the image's columns only, out_w is
                the computed width in whole sublanes)
                when taps pack, the patches (n_groups, tile_h, out_w, CI)
                f32; then the staging engine's refs.

    Without ``project`` (expansion ratio 1) the conv's c_mid block IS the
    output block: the cell writes act(conv).
    """
    s = stride
    if project:
        wproj_ref, o_ref, *scratch = refs
    else:
        wproj_ref, (o_ref, *scratch) = None, refs
    stage_refs, (conv_ref, *rest) = plan.take_scratch(tuple(scratch))
    per_pass = taps_per_pass(c_in, k_h * k_w)
    proj_refs = rest[:1] if project else ()
    patch_ref = rest[-1] if per_pass > 1 else None
    ci_dim = 4 if project else 3
    ci = pl.program_id(ci_dim)
    n_ci = pl.num_programs(ci_dim)
    if project:
        cm = pl.program_id(3)
        n_cm = pl.num_programs(3)
    win = StripStream(plan, x_ref, stage_refs).get()

    # Dense-conv tap loop: each group of ``taps_per_pass`` taps is one
    # (tile_h * out_w, CI) x (CI, CM) MXU contraction against its packed
    # weight plane — the expand-PW and DW of a classic MBConv in
    # ceil(k*k / T) passes.  patch_ref[g] holds group g's patch: its
    # first tap's strided window slice plus each tap t's rolled t * c_in
    # lanes up (lanes from c_in up are the window's zero padding).  A
    # strip's patches serve all its c_mid blocks, which the grid visits
    # in order inside the strip, so the first packs them, about 256 patch
    # rows a loop step.  Rolled whole, each tap's slice keeps VMEM of its
    # own (V2-S block 0 at tile_h 16 then needs 18.0 MiB, against 13.6 MiB
    # this way).  Each product goes straight into conv_ref: summed as
    # values, all k*k slices stay live, each split into three bf16 parts
    # under "highest" matmul precision (V2-S block 2 at tile_h 16 then
    # needs 20.0 MiB, against 13.6 MiB).  All compiled for a described
    # v5e.
    @pl.when(ci == 0)
    def _init():
        conv_ref[...] = jnp.zeros(conv_ref.shape, jnp.float32)

    step_rows = max(d for d in range(1, tile_h + 1)
                    if tile_h % d == 0 and d * out_w <= max(256, out_w))

    def pack_rows(g, group):
        def rows(r, carry):
            r0 = r * step_rows
            patch = None
            for t, (j, i) in enumerate(group):
                xs = win.read(j + r0 * s, i, step_rows, out_w, s).astype(
                    jnp.float32).reshape(step_rows * out_w, -1)
                patch = xs if t == 0 else patch + pltpu.roll(xs, t * c_in, 1)
            patch_ref[g, pl.ds(r0, step_rows)] = patch.reshape(
                step_rows, out_w, -1)
            return carry
        jax.lax.fori_loop(0, tile_h // step_rows, rows, 0)

    taps = [(j, i) for j in range(k_h) for i in range(k_w)]
    groups = [taps[g * per_pass:(g + 1) * per_pass]
              for g in range(wconv_ref.shape[0])]
    if per_pass > 1:
        def pack():
            for g, group in enumerate(groups):
                pack_rows(g, group)
        if project:
            pl.when(cm == 0)(pack)
        else:
            pack()
    for g, group in enumerate(groups):
        if per_pass > 1:
            patch = patch_ref[g]
        else:
            (j, i), = group
            patch = win.read(j, i, tile_h, out_w, s).astype(jnp.float32)
        conv_ref[...] += jax.lax.dot_general(
            patch.reshape(tile_h * out_w, patch.shape[-1]),
            wconv_ref[g].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(tile_h, out_w, -1)

    @pl.when(ci == n_ci - 1)
    def _finish():
        e = _act_ref(conv_ref[...], act)
        if not project:
            store_out(o_ref, e)
            return
        (proj_ref,) = proj_refs
        partial = jax.lax.dot_general(
            e.reshape(tile_h * out_w, e.shape[-1]),
            wproj_ref[:, :].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(tile_h, out_w, -1)

        @pl.when(cm == 0)
        def _proj_init():
            proj_ref[...] = partial

        @pl.when(cm > 0)
        def _proj_accumulate():
            proj_ref[...] = proj_ref[...] + partial

        @pl.when(cm == n_cm - 1)
        def _finalize():
            store_out(o_ref, proj_ref[...])


def fusedmb_pallas(x, w_conv, w_proj, *, k, c_in, geo: LaunchGeometry,
                   stride, ci_block, cm_block, co_block, act, interpret,
                   residency=DEFAULT_RESIDENCY):
    """Raw single-pass launch over the block's activation.

    x      : (B, H, W, CI_pad), unpadded spatially (``geo`` places its
             conv halo, which the staging engine builds in VMEM), zero
             from lane ``c_in`` up
    w_conv : (n_groups, CI_pad, CM_pad), the ``k`` = (k_h, k_w) conv's
             tap planes packed by ``pack_conv_taps``
    w_proj : (CM_pad, CO_pad), or None: no projection (expansion ratio
             1), the grid has no c_mid dim and the output is act(conv)
             over CO_pad = CM_pad in blocks of ``cm_block``
    returns (B, out_h, out_w, CO_pad)
    """
    b, h, w_in, ci_pad = x.shape
    k_h, k_w = k
    n_groups, _, cm_pad = w_conv.shape
    tile_h, out_wk = geo.tile_h, geo.out_wk
    project = w_proj is not None
    if project:
        co_pad = w_proj.shape[1]
        grid = (b, co_pad // co_block, geo.n_th, cm_pad // cm_block,
                ci_pad // ci_block)
    else:
        co_pad, co_block = cm_pad, cm_block
        grid = (b, cm_pad // cm_block, geo.n_th, ci_pad // ci_block)
    ci_dim = len(grid) - 1

    plan = strip_plan(
        h_tot=geo.h_tot, w_tot=geo.w_tot, c_block=ci_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 2, ci_dim), stride=stride, k_h=k_h,
        residency=residency, h_src=h, w_src=w_in, top=geo.top,
        left=geo.left)
    kernel = functools.partial(
        _fusedmb_kernel, plan=plan, project=project, k_h=k_h, k_w=k_w,
        c_in=c_in, stride=stride, tile_h=tile_h, out_w=out_wk, act=act)
    # the conv's c_mid block: the last-but-one dim with a projection, the
    # output's channel block without one
    cm_dim = 3 if project else 1
    in_specs = [
        plan.in_spec(),
        pl.BlockSpec((n_groups, ci_block, cm_block),
                     lambda *g: (0, g[ci_dim], g[cm_dim])),
    ]
    operands = [x, w_conv]
    scratch = [pltpu.VMEM((tile_h, out_wk, cm_block), jnp.float32)]
    if project:
        in_specs.append(pl.BlockSpec((cm_block, co_block),
                                     lambda bi, co, ti, cm, ci: (cm, co)))
        operands.append(w_proj)
        scratch.append(pltpu.VMEM((tile_h, out_wk, co_block), jnp.float32))
    if taps_per_pass(c_in, k_h * k_w) > 1:
        scratch.append(pltpu.VMEM((n_groups, tile_h, out_wk, ci_block),
                                  jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        # the image's pixels only: the last strip's block is clipped where
        # tile_h does not divide out_h
        out_specs=pl.BlockSpec(
            (1, tile_h, geo.out_w, co_block),
            lambda bi, co, ti, *_: (bi, ti, 0, co)),
        out_shape=jax.ShapeDtypeStruct(
            (b, geo.out_h, geo.out_w, co_pad), x.dtype),
        scratch_shapes=[*scratch, *plan.scratch_shapes(x.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="fusedmb",
    )(*operands)


def _fusedmb_impl(x, w_conv, w_proj, stride, padding, tile_h, act, interpret,
                  residency=DEFAULT_RESIDENCY,
                  axis_name: Optional[str] = None,
                  collective: str = DEFAULT_COLLECTIVE,
                  scatter_width: int = 0):
    """Single-pass Fused-MBConv on one device — or one SHARD of the c_mid
    grid when ``axis_name`` names a mesh axis (``shard_map`` body).

    Under c_mid sharding each device's dense conv is channel-local (it
    holds all of c_in — a dense conv cannot consume a sharded arrival),
    and the projection's c_mid reduction crosses devices per
    ``collective`` exactly like MBConv's pass 2: ``psum`` replicates the
    output, ``psum_scatter`` leaves it c_out-sharded at half the wire
    words.  There is no SE stage, hence no squeeze collective at all.

    ``w_proj is None`` (expansion ratio 1) is a block with no projection:
    the output is act(conv) over c_out = c_mid, and under c_mid sharding
    each device's output is its own channel slice, with no collective.

    ``x`` takes either layout, as in ``convdk_mbconv._mbconv_impl``: c_in
    channels (read from ``w_conv``) or the chain's lane layout, which the
    one-device output keeps.
    """
    validate_collective(collective)
    b, h, w_in, c_x = x.shape
    k_h, k_w, c_in, c_mid = w_conv.shape
    lanes = in_lane_layout(x)
    assert c_x in (c_in, _round_up(c_in, 128)), (x.shape, w_conv.shape)
    if w_proj is None:
        c_out = c_mid
    else:
        c_out = w_proj.shape[1]
        assert w_proj.shape[0] == c_mid, (w_proj.shape, c_mid)
    s = stride
    geo = launch_geometry(h, w_in, k_h, k_w, s, padding, tile_h)

    ci_block = pick_channel_block(c_in)
    ci_pad = _round_up(c_in, ci_block)
    cm_block = pick_channel_block(c_mid)
    cm_pad = _round_up(c_mid, cm_block)
    co_block = pick_channel_block(c_out)
    co_pad = _round_up(c_out, co_block)

    wconv_p = pack_conv_taps(w_conv, ci_pad, cm_pad)
    wproj_p = None if w_proj is None else jnp.pad(
        w_proj, ((0, cm_pad - c_mid), (0, co_pad - c_out)))

    out = fusedmb_pallas(
        strip_source(to_lanes(x), ci_block), wconv_p, wproj_p,
        k=(k_h, k_w), c_in=c_in, stride=s, geo=geo, ci_block=ci_block,
        cm_block=cm_block, co_block=co_block, act=act, interpret=interpret,
        residency=residency)
    if axis_name is None:
        return as_layout(out, c_out, lanes)
    if w_proj is None:
        return out[..., :c_out]
    if collective == "psum_scatter":
        # layout-aware exit, same contract as MBConv pass 2: zero w_proj
        # columns pad a non-dividing c_out to ``scatter_width`` (their
        # partials are exactly zero), the wrapper slices them back.
        cw = scatter_width if scatter_width else c_out
        out = out[..., :min(cw, out.shape[-1])]
        if out.shape[-1] < cw:
            out = jnp.pad(
                out, ((0, 0), (0, 0), (0, 0), (0, cw - out.shape[-1])))
        return jax.lax.psum_scatter(out, axis_name,
                                    scatter_dimension=3, tiled=True)
    # projection partials: each shard contracted only its c_mid slice
    return jax.lax.psum(out[..., :c_out], axis_name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fusedmb_op(x, w_conv, w_proj, stride, padding, tile_h, act, interpret,
                residency):
    return _fusedmb_impl(x, w_conv, w_proj, stride, padding, tile_h, act,
                         interpret, residency)


def _fusedmb_fwd(x, w_conv, w_proj, stride, padding, tile_h, act, interpret,
                 residency):
    out = _fusedmb_op(x, w_conv, w_proj, stride, padding, tile_h, act,
                      interpret, residency)
    return out, (x, w_conv, w_proj)


def _fusedmb_bwd(stride, padding, tile_h, act, interpret, residency, res, g):
    # Backward through the mathematically identical reference composition —
    # the single-pass kernel computes the same Fused-MBConv block, so the
    # VJP is exact (same pattern as convdk_fused / convdk_mbconv).  A
    # lane-layout input and output are the reference's narrow ones with a
    # zero tail.
    x, w_conv, w_proj = res
    c_in, c_mid = w_conv.shape[2:]
    c_out = c_mid if w_proj is None else w_proj.shape[1]
    lanes = in_lane_layout(x)
    _, vjp = jax.vjp(
        lambda x_, wc_, wp_: as_layout(fusedmb_ref(
            x_[..., :c_in], wc_, wp_, stride=stride, padding=padding,
            act=act), c_out, lanes),
        x, w_conv, w_proj,
    )
    return vjp(g)


_fusedmb_op.defvjp(_fusedmb_fwd, _fusedmb_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "act", "interpret",
                     "residency"),
)
def _fusedmb_fused(x, w_conv, w_proj, *, stride, padding, tile_h, act,
                   interpret, residency):
    return _fusedmb_op(x, w_conv, w_proj, stride, padding, tile_h, act,
                       interpret, residency)


def convdk_fusedmb_fused(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: Optional[jax.Array],
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    act: Optional[str] = "silu",
    interpret: Optional[bool] = None,
    residency: Optional[str] = None,
) -> jax.Array:
    """Single-pass fused Fused-MBConv block via one ConvDK Pallas kernel
    (differentiable).  No residual add — the model layer owns that.

    x      : (B, H, W, C_in) NHWC, or the block chain's lane layout: C_in
             rounded up to whole 128-lane groups with a zero tail
             (``common.in_lane_layout``)
    w_conv : (k_h, k_w, C_in, C_mid) HWIO dense conv (the collapsed
             expand+DW of EfficientNet-V2's fused stages)
    w_proj : (C_mid, C_out) projection PW (linear), or None: no
             projection (expansion ratio 1), the block is act(conv) and
             C_out = C_mid
    act    : conv activation (EfficientNet-V2 uses silu)
    residency : "resident" | "strip_dma" | "strip_dma_db" (default) — how
             the input stream is staged (``kernels.staging``).
    Returns (B, H', W', C_out), or (B, H', W', round_up(C_out, 128)) with
    a zero tail for an input in the lane layout.  The expanded (C_mid)
    tensor never touches HBM; there is no SE stage and no second pass.

    A call with ``w_proj=None`` ticks ``kernels.fusedmb.no_project`` once:
    once per launch site a jitted caller traces (two blocks of one shape
    share a compiled kernel but are two launches), once per launch when
    called eagerly.  A call whose kernel packs taps (``taps_per_pass`` of
    C_in over 1) ticks ``kernels.fusedmb.tap_packed`` the same way, and
    a call whose kernel stages its input as it is, building the whole
    conv halo in VMEM (``common.stages_in_place``), ticks
    ``kernels.staging.halo_in_vmem``.
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    if w_proj is None:
        telemetry.counter("kernels.fusedmb.no_project")
    k_h, k_w, c_in, _ = w_conv.shape
    if taps_per_pass(c_in, k_h * k_w) > 1:
        telemetry.counter("kernels.fusedmb.tap_packed")
    if stages_in_place(x):
        telemetry.counter("kernels.staging.halo_in_vmem")
    return _fusedmb_fused(x, w_conv, w_proj, stride=stride, padding=padding,
                          tile_h=tile_h, act=act, interpret=interpret,
                          residency=residency)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "act", "interpret"),
)
def convdk_fusedmb_staged(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: Optional[jax.Array],
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    act: Optional[str] = "silu",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The STAGED Fused-MBConv pipeline (comparison baseline,
    differentiable): dense conv -> HBM -> act -> HBM -> projection einsum.
    The expanded (B, H', W', C_mid) tensor round-trips through HBM exactly
    as the weight-stationary baseline, which is what
    ``convdk_fusedmb_fused`` eliminates.  ``tile_h`` is accepted for
    call-site symmetry with the fused entry; the staged rendering has no
    strip structure.  ``w_proj=None``: no projection, the activation is
    the output.  Takes and returns either layout, as the fused entry does.
    """
    del tile_h
    if interpret is None:
        interpret = default_interpret()
    lanes = in_lane_layout(x)
    e = jax.lax.conv_general_dilated(
        x[..., :w_conv.shape[2]].astype(jnp.float32),
        w_conv.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    out = _act_ref(e, act)
    if w_proj is not None:
        out = jnp.einsum("bhwc,cd->bhwd", out, w_proj.astype(jnp.float32))
    return as_layout(out.astype(x.dtype), out.shape[-1], lanes)
