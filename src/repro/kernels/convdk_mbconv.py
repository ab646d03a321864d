"""Two-pass fused MBConv (EfficientNet) ConvDK Pallas kernels.

EfficientNet's MBConv inserts squeeze-and-excitation between the depthwise
and projection stages:

    expand 1x1 -> act -> DW k x k / s -> act -> SE(global pool -> MLP ->
    sigmoid gate) -> project 1x1 (+ residual)

The SE *squeeze* is a global pool over the whole DW output, so the
single-strip VMEM residency of ``convdk_fused_separable`` cannot cover the
block: the projection of any strip depends on every strip's DW output.  The
staged rendering therefore round-trips the full expanded DW tensor through
HBM four extra times (DW write, pool read, gate read+write, projection
read) — exactly the weight-stationary baseline traffic the paper eliminates
for plain separable blocks.

This module closes the gap with a **two-pass fused schedule**:

* **Pass 1** (``_mbconv_pass1_kernel``): per (c_mid block, row strip), the
  expand PW runs over the staged input window (reduction over c_in blocks
  in the innermost grid dim), the DW taps consume the expanded strip while
  it is still in VMEM, and the SE pool is accumulated on-chip into a tiny
  (B, C_mid) output — masked so padded strip rows never enter the pool.
  The DW output either goes to HBM ONCE (``mode="retain"``) or is
  discarded (``mode="recompute"``).
* **SE MLP** (host-side, between passes): two tiny FCs + sigmoid on the
  pooled (B, C_mid) vector — negligible traffic, accounted by the model.
* **Pass 2**: the SE gate folds into the projection contraction in the same
  VMEM residency as the DW block — read back from HBM (``retain``,
  ``_mbconv_pass2_retain_kernel``) or recomputed from the input strips
  (``recompute``, ``_mbconv_pass2_recompute_kernel``, same expand+DW loop
  as pass 1).  The only activation write of the whole block is the final
  output.

Every big input stream goes through the shared strip-staging engine
(``kernels.staging``) under the schedule's **residency** axis: the input
windows of pass 1 / recompute pass 2 are halo'd conv strips, and the
``retain`` pass-2 re-read of the DW tensor is a non-overlapping row-block
stream — under ``strip_dma_db`` it becomes a double-buffered DMA stream
that prefetches the next (strip, c_mid block) while the projection of the
current one runs.

Retain pays ``E * (1 + n_co)`` HBM words for the DW tensor ``E``; recompute
re-reads the input strips and expand/DW weights ``n_co`` more times.  The
crossover is priced per layer shape by ``core.perfmodel.mbconv_fused_traffic``
and chosen by ``core.autotune.select_mbconv_schedule`` (MIREDO-style: the
schedule is solved per block topology, not per op).

Blocks with expansion ratio 1 (EfficientNet's MBConv1, MobileNet-V3's first
bottleneck) pass ``w_exp=None`` with ``exp_act=None``: there is no expand
stage at all.  Their launches drop the c_in grid dimension, the expand
weight and the expand accumulator; the staged window's channel block
follows the c_mid grid dimension and the depthwise taps read the window
itself.  A square ``w_exp`` a caller does pass still runs as a real expand.
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
from .ref import _act_ref, mbconv_ref
from .staging import StripPlan, StripStream, StripWindow, strip_plan


def _dw_taps(win: StripWindow, w_dw_ref, *, dw_act, k_h, k_w, stride, tile_h,
             out_w):
    """Algorithm-2 tap loop over a (in_rows, w_tot, CM) window ->
    (tile_h, out_w, CM) f32.  Each tap is one (strided, for s = 2) load
    from the window's ref — Mosaic cannot stride-slice a loaded value."""
    dw = jnp.zeros((tile_h, out_w, w_dw_ref.shape[-1]), jnp.float32)
    for j in range(k_h):
        for i in range(k_w):
            xs = win.read(j, i, tile_h, out_w, stride)
            dw = dw + xs.astype(jnp.float32) * w_dw_ref[j, i].astype(
                jnp.float32)
    return _act_ref(dw, dw_act)


def _expand_accumulate(win, wexp_ref, acc_ref, *, ci):
    """One c_in-block partial of the expand PW over the staged strip window.

    ``win`` is the engine-staged ``(in_rows, w_tot, CI)`` window; the
    contraction with the (CI, CM) expand block accumulates across the
    innermost c_in grid dimension.
    """
    in_rows, w_tot = win.shape[0], win.shape[1]
    partial = jax.lax.dot_general(
        win.reshape(in_rows * w_tot, win.shape[-1]).astype(jnp.float32),
        wexp_ref[:, :].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(in_rows, w_tot, -1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = partial

    @pl.when(ci > 0)
    def _accumulate():
        acc_ref[...] = acc_ref[...] + partial


def _strip_dw(win: StripWindow, wexp_ref, wdw_ref, acc_refs, *, ci_dim,
              exp_act, on_dw, **taps):
    """Call ``on_dw(dw)`` with this cell's finished DW strip.

    With an expand (``wexp_ref`` given), the window's c_in block
    accumulates into ``acc_refs[0]`` across grid dim ``ci_dim``; at the
    last c_in block the expand activation is applied to the accumulator IN
    PLACE (the taps load from the ref) and the taps run over it.  Without
    one (expansion ratio 1) the taps read the staged window itself.
    """
    if wexp_ref is None:
        on_dw(_dw_taps(win, wdw_ref, **taps))
        return
    (acc_ref,) = acc_refs
    ci = pl.program_id(ci_dim)
    _expand_accumulate(win.read(), wexp_ref, acc_ref, ci=ci)

    @pl.when(ci == pl.num_programs(ci_dim) - 1)
    def _finish_strip():
        if exp_act is not None:
            acc_ref[...] = _act_ref(acc_ref[...], exp_act)
        acc = StripWindow(acc_ref, (), 0, *acc_ref.shape[:2])
        on_dw(_dw_taps(acc, wdw_ref, **taps))


def _mbconv_pass1_kernel(x_ref, *refs, plan: StripPlan, expand: bool, k_h,
                         k_w, stride, tile_h, out_w, out_h, valid_w,
                         exp_act: Optional[str], dw_act: Optional[str],
                         se: bool, retain: bool):
    """One (batch, c_mid-block, row-strip[, c_in-block]) grid cell of
    pass 1 (the c_in dim only when ``expand``).

    x_ref    : unstaged input (engine-staged per ``plan``)
    wexp_ref : (CI, CM)               expand-PW block, only when ``expand``
    wdw_ref  : (k_h, k_w, CM)         depthwise taps
    then     : (pool_ref,) if se — the (1, 1, CM) on-chip SE pool
               accumulator (sums) — then (dw_out_ref,) if retain, then
               acc_ref (only when ``expand``) + staging refs.  An se=off
               launch carries NO pool output at all: the no-SE block pays
               zero pool VMEM/HBM.
    """
    if expand:
        wexp_ref, wdw_ref, *rest = refs
    else:
        wexp_ref, (wdw_ref, *rest) = None, refs
    if se:
        pool_ref, *rest = rest
    if retain:
        dwo_ref, *rest = rest
    stage_refs, acc_refs = plan.take_scratch(tuple(rest))
    ti = pl.program_id(2)
    win = StripStream(plan, x_ref, stage_refs).get()

    def finish(dw):
        if se:
            # mask strip rows past out_h and the sublane-cover columns past
            # valid_w so they never enter the pool (full-rank iotas: Mosaic
            # cannot broadcast a 2-D mask up)
            rows = jax.lax.broadcasted_iota(jnp.int32, dw.shape, 0) \
                + ti * tile_h
            cols = jax.lax.broadcasted_iota(jnp.int32, dw.shape, 1)
            masked = jnp.where((rows < out_h) & (cols < valid_w), dw, 0.0)
            sums = jnp.sum(masked, axis=(0, 1), keepdims=True)  # (1, 1, CM)

            @pl.when(ti == 0)
            def _pool_init():
                pool_ref[...] = sums

            @pl.when(ti > 0)
            def _pool_accumulate():
                pool_ref[...] = pool_ref[...] + sums

        if retain:
            dwo_ref[0] = dw.astype(dwo_ref.dtype)

    _strip_dw(win, wexp_ref, wdw_ref, acc_refs, ci_dim=3, exp_act=exp_act,
              on_dw=finish, dw_act=dw_act, k_h=k_h, k_w=k_w, stride=stride,
              tile_h=tile_h, out_w=out_w)


def _mbconv_pass2_recompute_kernel(x_ref, *refs, plan: StripPlan,
                                   expand: bool, k_h, k_w, stride, tile_h,
                                   out_w, exp_act: Optional[str],
                                   dw_act: Optional[str], se: bool):
    """One (batch, c_out-block, row-strip, c_mid-block[, c_in-block]) cell
    (the c_in dim only when ``expand``).

    Recomputes expand+DW exactly as pass 1 (the DW tensor never existed in
    HBM), multiplies by the SE gate (when ``se`` — an se=off launch carries
    no scale input at all) and contracts with the projection block —
    partial projection sums carried across the c_mid grid dimension.
    """
    if expand:
        wexp_ref, wdw_ref, *rest = refs
    else:
        wexp_ref, (wdw_ref, *rest) = None, refs
    if se:
        scale_ref, *rest = rest
    wproj_ref, o_ref, *scratch = rest
    stage_refs, (*acc_refs, proj_ref) = plan.take_scratch(tuple(scratch))
    cm = pl.program_id(3)
    n_cm = pl.num_programs(3)
    win = StripStream(plan, x_ref, stage_refs).get()

    def project(dw):
        if se:
            dw = dw * scale_ref[0, 0].astype(jnp.float32)
        partial = jax.lax.dot_general(
            dw.reshape(tile_h * out_w, dw.shape[-1]),
            wproj_ref[:, :].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(tile_h, out_w, -1)

        @pl.when(cm == 0)
        def _init():
            proj_ref[...] = partial

        @pl.when(cm > 0)
        def _accumulate():
            proj_ref[...] = proj_ref[...] + partial

        @pl.when(cm == n_cm - 1)
        def _finalize():
            store_out(o_ref, proj_ref[...])

    _strip_dw(win, wexp_ref, wdw_ref, acc_refs, ci_dim=4, exp_act=exp_act,
              on_dw=project, dw_act=dw_act, k_h=k_h, k_w=k_w, stride=stride,
              tile_h=tile_h, out_w=out_w)


def _mbconv_pass2_retain_kernel(dw_ref, *rest, plan: StripPlan, tile_h,
                                out_w, se: bool):
    """One (batch, c_out-block, row-strip, c_mid-block) cell: stage the
    retained DW block back (a non-overlapping row-block stream — double-
    buffered DMA under ``strip_dma_db``), fold in the SE gate (when ``se``
    — an se=off launch carries no scale input), contract with the
    projection block (partial sums across the c_mid grid dim)."""
    rest = tuple(rest)
    if se:
        scale_ref, *rest = rest
    wproj_ref, o_ref, *scratch = rest
    stage_refs, (proj_ref,) = plan.take_scratch(tuple(scratch))
    cm = pl.program_id(3)
    n_cm = pl.num_programs(3)
    dw = StripStream(plan, dw_ref, stage_refs).get().read()
    dw = dw.astype(jnp.float32)
    if se:
        dw = dw * scale_ref[0, 0].astype(jnp.float32)
    partial = jax.lax.dot_general(
        dw.reshape(tile_h * out_w, dw.shape[-1]),
        wproj_ref[:, :].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(tile_h, out_w, -1)

    @pl.when(cm == 0)
    def _init():
        proj_ref[...] = partial

    @pl.when(cm > 0)
    def _accumulate():
        proj_ref[...] = proj_ref[...] + partial

    @pl.when(cm == n_cm - 1)
    def _finalize():
        store_out(o_ref, proj_ref[...])


def _input_stream(x, w_exp, w_dw, head, cm_dim, *, geo: LaunchGeometry,
                  stride, ci_block, cm_block, residency):
    """Grid, staged-input plan, and the (x, [w_exp,] w_dw) in_specs and
    operands that pass 1 and recompute pass 2 share.

    ``x`` is the block's activation in the lane layout; the plan builds
    its conv halo in VMEM.  ``head`` is the kernel's grid without the c_in
    dim and ``cm_dim`` its c_mid dim.  A real expand appends the c_in
    reduction dim, which picks the window's channel block.  ``w_exp=None``
    (expansion ratio 1) adds no dim: the window's channel block is the
    c_mid block the taps read.
    """
    b, h, w_in, ci_pad = x.shape
    k_h, k_w, cm_pad = w_dw.shape
    if w_exp is not None:
        grid, chan_dim = (*head, ci_pad // ci_block), len(head)
    else:
        assert (ci_pad, ci_block) == (cm_pad, cm_block), (
            ci_pad, ci_block, cm_pad, cm_block)
        telemetry.counter("kernels.mbconv.identity_expand")
        grid, chan_dim = tuple(head), cm_dim
    plan = strip_plan(
        h_tot=geo.h_tot, w_tot=geo.w_tot, c_block=ci_block,
        tile_h=geo.tile_h, grid=grid, window_dims=(0, 2, chan_dim),
        stride=stride, k_h=k_h, residency=residency, h_src=h, w_src=w_in,
        top=geo.top, left=geo.left)
    in_specs = [plan.in_spec()]
    operands = [x]
    if w_exp is not None:
        in_specs.append(pl.BlockSpec((ci_block, cm_block),
                                     lambda *g: (g[chan_dim], g[cm_dim])))
        operands.append(w_exp)
    in_specs.append(pl.BlockSpec((k_h, k_w, cm_block),
                                 lambda *g: (0, 0, g[cm_dim])))
    operands.append(w_dw)
    return grid, plan, in_specs, operands


def _expand_scratch(w_exp, plan: StripPlan, cm_block) -> list:
    """The f32 expand accumulator — none without an expand."""
    if w_exp is None:
        return []
    return [pltpu.VMEM((plan.in_rows, plan.w_tot, cm_block), jnp.float32)]


def mbconv_pass1_pallas(x, w_exp, w_dw, *, geo: LaunchGeometry, stride,
                        ci_block, cm_block, exp_act, dw_act, retain,
                        interpret, se=True, residency=DEFAULT_RESIDENCY):
    """Raw pass-1 launch: (pool_sums-or-None, dw_retained-or-None).

    ``x`` is the block's activation in the lane layout, unpadded; ``geo``
    places its conv halo.  The kernel computes ``geo.out_wk`` (whole
    sublane) output columns; the pool counts only the first ``out_w``
    columns and ``out_h`` rows.

    ``se=False`` drops the pool output (and its VMEM accumulator) from the
    launch entirely — an se=off retain pass writes only the DW tensor.
    ``w_exp=None`` launches the block with no expand stage.
    """
    assert se or retain, "se=off + recompute has no pass 1 at all"
    b = x.shape[0]
    cm_pad = w_dw.shape[-1]
    tile_h, out_wk = geo.tile_h, geo.out_wk
    grid, plan, in_specs, operands = _input_stream(
        x, w_exp, w_dw, (b, cm_pad // cm_block, geo.n_th), 1, geo=geo,
        stride=stride, ci_block=ci_block, cm_block=cm_block,
        residency=residency)
    kernel = functools.partial(
        _mbconv_pass1_kernel, plan=plan, expand=w_exp is not None,
        k_h=w_dw.shape[0], k_w=w_dw.shape[1], stride=stride, tile_h=tile_h,
        out_w=out_wk, out_h=geo.out_h, valid_w=geo.out_w, exp_act=exp_act,
        dw_act=dw_act, se=se, retain=retain)
    out_shape = []
    out_specs = []
    if se:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, cm_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, cm_block),
                                      lambda bi, cm, ti, *_: (bi, 0, cm)))
    if retain:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, geo.n_th * tile_h, out_wk, cm_pad), x.dtype))
        out_specs.append(pl.BlockSpec(
            (1, tile_h, out_wk, cm_block),
            lambda bi, cm, ti, *_: (bi, ti, 0, cm)))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            *_expand_scratch(w_exp, plan, cm_block),
            *plan.scratch_shapes(x.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="mbconv_pass1",
    )(*operands)
    outs = list(outs)
    pool = outs.pop(0) if se else None
    dw_ret = outs.pop(0) if retain else None
    return pool, dw_ret


def _output(geo: LaunchGeometry, b, co_pad, co_block, dtype):
    """(out_spec, out_shape) of a pass-2 launch: the image's ``out_h`` x
    ``out_w`` pixels in the lane layout, no cover — the last strip's
    block is clipped where ``tile_h`` does not divide ``out_h``."""
    return (pl.BlockSpec((1, geo.tile_h, geo.out_w, co_block),
                         lambda bi, co, ti, *_: (bi, ti, 0, co)),
            jax.ShapeDtypeStruct((b, geo.out_h, geo.out_w, co_pad), dtype))


def mbconv_pass2_recompute_pallas(x, w_exp, w_dw, scale, w_proj, *,
                                  geo: LaunchGeometry, stride, ci_block,
                                  cm_block, co_block, exp_act, dw_act,
                                  interpret, residency=DEFAULT_RESIDENCY):
    """``scale=None`` launches the se=off variant: no gate input, no gate
    multiply — the no-SE block pays zero scale bytes.  ``w_exp=None``
    launches the block with no expand stage.  Returns (B, out_h, out_w,
    CO_pad)."""
    se = scale is not None
    b = x.shape[0]
    cm_pad = w_dw.shape[-1]
    co_pad = w_proj.shape[1]
    tile_h, out_wk = geo.tile_h, geo.out_wk
    grid, plan, in_specs, operands = _input_stream(
        x, w_exp, w_dw,
        (b, co_pad // co_block, geo.n_th, cm_pad // cm_block), 3, geo=geo,
        stride=stride, ci_block=ci_block, cm_block=cm_block,
        residency=residency)
    kernel = functools.partial(
        _mbconv_pass2_recompute_kernel, plan=plan, expand=w_exp is not None,
        k_h=w_dw.shape[0], k_w=w_dw.shape[1], stride=stride, tile_h=tile_h,
        out_w=out_wk, exp_act=exp_act, dw_act=dw_act, se=se)
    if se:
        in_specs.append(pl.BlockSpec((1, 1, cm_block),
                                     lambda bi, co, ti, cm, *_: (bi, 0, cm)))
        operands.append(scale)
    in_specs.append(pl.BlockSpec((cm_block, co_block),
                                 lambda bi, co, ti, cm, *_: (cm, co)))
    operands.append(w_proj)
    out_spec, out_shape = _output(geo, b, co_pad, co_block, x.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            *_expand_scratch(w_exp, plan, cm_block),
            pltpu.VMEM((tile_h, out_wk, co_block), jnp.float32),
            *plan.scratch_shapes(x.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="mbconv_pass2_recompute",
    )(*operands)


def mbconv_pass2_retain_pallas(dw_ret, scale, w_proj, *,
                               geo: LaunchGeometry, cm_block, co_block,
                               interpret, residency=DEFAULT_RESIDENCY):
    b = dw_ret.shape[0]
    cm_pad = dw_ret.shape[-1]
    co_pad = w_proj.shape[1]
    tile_h, out_wk = geo.tile_h, geo.out_wk
    grid = (b, co_pad // co_block, geo.n_th, cm_pad // cm_block)

    # The retained-DW re-read: non-overlapping tile_h-row blocks (k_h=1,
    # stride=1 geometry) — the double-buffered DMA stream of the tentpole.
    se = scale is not None
    plan = strip_plan(
        h_tot=dw_ret.shape[1], w_tot=out_wk, c_block=cm_block,
        tile_h=tile_h, grid=grid, window_dims=(0, 2, 3),
        residency=residency)
    kernel = functools.partial(_mbconv_pass2_retain_kernel, plan=plan,
                               tile_h=tile_h, out_w=out_wk, se=se)
    in_specs = [plan.in_spec(lambda bi, co, ti, cm: (bi, ti, 0, cm))]
    operands = [dw_ret]
    if se:
        in_specs.append(pl.BlockSpec((1, 1, cm_block),
                                     lambda bi, co, ti, cm: (bi, 0, cm)))
        operands.append(scale)
    in_specs.append(pl.BlockSpec((cm_block, co_block),
                                 lambda bi, co, ti, cm: (cm, co)))
    operands.append(w_proj)
    out_spec, out_shape = _output(geo, b, co_pad, co_block, dw_ret.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tile_h, out_wk, co_block), jnp.float32),
                        *plan.scratch_shapes(dw_ret.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="mbconv_pass2_retain",
    )(*operands)


def _mbconv_impl(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, stride,
                 padding, tile_h, mode, exp_act, dw_act, interpret,
                 residency=DEFAULT_RESIDENCY,
                 se_act: Optional[str] = "silu",
                 gate_act: Optional[str] = "sigmoid",
                 axis_name: Optional[str] = None,
                 collective: str = DEFAULT_COLLECTIVE,
                 scatter_width: int = 0):
    """Two-pass fused MBConv on one device — or on one SHARD of the c_mid
    grid when ``axis_name`` names a mesh axis (``shard_map`` body).

    Under c_mid sharding every device runs pass 1 / pass 2 on its own
    channel slice, and the two contractions over the full expanded width
    become cross-device reductions:

    * the SE squeeze FC (``mean @ w_se1`` reduces over C_mid) — the pass-1
      pool leaves the chip exactly once, as a tiny (B, C_se) partial,
      always a full ``psum`` (the excite FC consumes it replicated);
    * the projection PW (``dw @ w_proj`` reduces over C_mid) — each device
      contributes its channel slice's partial output.  This is the
      **collective axis hook**: ``collective == "ring_allreduce"`` emits
      ``jax.lax.psum`` (output replicated), ``"psum_scatter"`` emits
      ``jax.lax.psum_scatter`` over the channel dim — half the wire
      words, and the pass-2 output leaves the kernel SHARDED on c_out for
      a consumer that wants it that way.

    Everything else (expand columns, DW taps, the excite FC rows, the
    retained DW tensor) is local to the shard.

    ``w_se1 is None`` switches SE off (MobileNet-V3's no-SE blocks): the
    pass-1 pool output, the host MLP, the squeeze psum and the pass-2
    scale input all disappear — and under ``mode="recompute"`` pass 1 is
    skipped ENTIRELY (it would produce nothing).  ``se_act``/``gate_act``
    parameterize the SE MLP's nonlinearities (V3 uses relu/hard_sigmoid).

    ``w_exp is None`` (with ``exp_act=None``) is a block with no expand
    convolution: the kernels' taps read the staged input window directly.

    ``x`` is unpadded spatially; the kernels stage straight from it and
    build the conv halo in VMEM.  Its channels are either the block's
    c_in (read from the weights) or the lane layout the block chain
    carries (``common.in_lane_layout``: c_in rounded up to whole 128-lane
    groups, zero from c_in up).  On one device the output keeps the
    input's layout: c_out channels, or round_up(c_out, 128) with a zero
    tail (the padded projection columns are zero).
    """
    validate_collective(collective)
    se = w_se1 is not None
    k_h, k_w, c_mid = w_dw.shape
    if w_exp is None:
        assert exp_act is None, exp_act
        c_in = c_mid
    else:
        c_in = w_exp.shape[0]
        assert w_exp.shape == (c_in, c_mid), (w_exp.shape, c_in, c_mid)
    b, h, w_in, c_x = x.shape
    lanes = in_lane_layout(x)
    assert c_x in (c_in, _round_up(c_in, 128)), (x.shape, c_in)
    c_out = w_proj.shape[1]
    assert w_proj.shape[0] == c_mid, (w_proj.shape, c_mid)
    assert mode in ("retain", "recompute"), mode
    s = stride

    geo = launch_geometry(h, w_in, k_h, k_w, s, padding, tile_h)
    out_h, out_w = geo.out_h, geo.out_w

    ci_block = pick_channel_block(c_in)
    ci_pad = _round_up(c_in, ci_block)
    cm_block = pick_channel_block(c_mid)
    cm_pad = _round_up(c_mid, cm_block)
    co_block = pick_channel_block(c_out)
    co_pad = _round_up(c_out, co_block)

    # a caller's narrow array gets its lane tail here; the block chain
    # hands the kernels their own layout and nothing is copied
    xl = strip_source(to_lanes(x), ci_block)
    wexp_p = (None if w_exp is None else
              jnp.pad(w_exp, ((0, ci_pad - c_in), (0, cm_pad - c_mid))))
    wdw_p = jnp.pad(w_dw, ((0, 0), (0, 0), (0, cm_pad - c_mid)))
    wproj_p = jnp.pad(w_proj, ((0, cm_pad - c_mid), (0, co_pad - c_out)))

    if se or mode == "retain":
        pool, dw_ret = mbconv_pass1_pallas(
            xl, wexp_p, wdw_p, geo=geo, stride=s, ci_block=ci_block,
            cm_block=cm_block, exp_act=exp_act, dw_act=dw_act,
            retain=(mode == "retain"), interpret=interpret, se=se,
            residency=residency)
    else:
        # se=off + recompute: pass 1 would produce nothing — skip it.
        pool, dw_ret = None, None

    if se:
        # SE MLP on the on-chip-accumulated pool (masked rows excluded; the
        # mean uses the true output element count).  The squeeze FC reduces
        # over C_mid, so under c_mid sharding its partial product is psum'd
        # across the mesh axis before the bias + nonlinearity.
        mean = pool[:, 0, :c_mid] / float(out_h * out_w)      # (B, C_mid) f32
        squeeze = mean @ w_se1.astype(jnp.float32)
        if axis_name is not None:
            squeeze = jax.lax.psum(squeeze, axis_name)
        s1 = _act_ref(squeeze + b_se1.astype(jnp.float32), se_act)
        gate = _act_ref(s1 @ w_se2.astype(jnp.float32)
                        + b_se2.astype(jnp.float32), gate_act)
        scale = jnp.pad(gate, ((0, 0), (0, cm_pad - c_mid)))[:, None, :]
    else:
        scale = None

    if mode == "retain":
        out = mbconv_pass2_retain_pallas(
            dw_ret, scale, wproj_p, geo=geo, cm_block=cm_block,
            co_block=co_block, interpret=interpret, residency=residency)
    else:
        out = mbconv_pass2_recompute_pallas(
            xl, wexp_p, wdw_p, scale, wproj_p, geo=geo, stride=s,
            ci_block=ci_block, cm_block=cm_block, co_block=co_block,
            exp_act=exp_act, dw_act=dw_act, interpret=interpret,
            residency=residency)
    if axis_name is None:
        return as_layout(out, c_out, lanes)
    if collective == "psum_scatter":
        # reduce-scatter over the channel dim: (mp-1)/mp words per
        # reduced word instead of the ring's 2*(mp-1)/mp, and this
        # shard keeps only its channel slice — the layout-aware exit.
        # Non-dividing c_out scatters at ``scatter_width`` (the next
        # model-factor multiple): the extra columns are zero w_proj
        # columns, so their partials are exactly zero and the wrapper
        # slices them back off the gathered-global view.
        cw = scatter_width if scatter_width else c_out
        out = out[..., :min(cw, out.shape[-1])]
        if out.shape[-1] < cw:
            out = jnp.pad(
                out, ((0, 0), (0, 0), (0, 0), (0, cw - out.shape[-1])))
        return jax.lax.psum_scatter(out, axis_name,
                                    scatter_dimension=3, tiled=True)
    # projection partials: each shard contracted only its c_mid slice
    return jax.lax.psum(out[..., :c_out], axis_name)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(8, 9, 10, 11, 12, 13, 14, 15, 16, 17))
def _mbconv_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, stride,
               padding, tile_h, mode, exp_act, dw_act, interpret, residency,
               se_act="silu", gate_act="sigmoid"):
    return _mbconv_impl(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                        stride, padding, tile_h, mode, exp_act, dw_act,
                        interpret, residency, se_act=se_act,
                        gate_act=gate_act)


def _mbconv_fwd(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, stride,
                padding, tile_h, mode, exp_act, dw_act, interpret, residency,
                se_act="silu", gate_act="sigmoid"):
    out = _mbconv_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                     stride, padding, tile_h, mode, exp_act, dw_act,
                     interpret, residency, se_act, gate_act)
    return out, (x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj)


def _mbconv_bwd(stride, padding, tile_h, mode, exp_act, dw_act, interpret,
                residency, se_act, gate_act, res, g):
    # Backward through the mathematically identical reference composition —
    # the two-pass kernel computes the same MBConv block, so the VJP is
    # exact (same pattern as convdk_fused's VJP).  mbconv_ref skips the SE
    # stage for w_se1=None and the expand for w_exp=None, matching the
    # kernel paths; those params' cotangents come back as None there, as
    # custom_vjp expects.  A lane-layout input and output are the
    # reference's narrow ones with a zero tail.
    x, w_exp, w_dw, *_, w_proj = res
    c_in = w_dw.shape[-1] if w_exp is None else w_exp.shape[0]
    lanes = in_lane_layout(x)
    _, vjp = jax.vjp(
        lambda x_, *p: as_layout(
            mbconv_ref(x_[..., :c_in], *p, stride=stride, padding=padding,
                       exp_act=exp_act, dw_act=dw_act, se_act=se_act,
                       gate_act=gate_act), w_proj.shape[1], lanes),
        *res,
    )
    return vjp(g)


_mbconv_op.defvjp(_mbconv_fwd, _mbconv_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "mode", "exp_act",
                     "dw_act", "se_act", "gate_act", "interpret",
                     "residency"),
)
def _mbconv_fused(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, *,
                  stride, padding, tile_h, mode, exp_act, dw_act, se_act,
                  gate_act, interpret, residency):
    return _mbconv_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                      stride, padding, tile_h, mode, exp_act, dw_act,
                      interpret, residency, se_act, gate_act)


def convdk_mbconv_fused(
    x: jax.Array,
    w_exp: Optional[jax.Array],
    w_dw: jax.Array,
    w_se1: Optional[jax.Array],
    b_se1: Optional[jax.Array],
    w_se2: Optional[jax.Array],
    b_se2: Optional[jax.Array],
    w_proj: jax.Array,
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    mode: str = "retain",
    exp_act: Optional[str] = "silu",
    dw_act: Optional[str] = "silu",
    se_act: Optional[str] = "silu",
    gate_act: Optional[str] = "sigmoid",
    interpret: Optional[bool] = None,
    residency: Optional[str] = None,
) -> jax.Array:
    """Two-pass fused MBConv block via the ConvDK Pallas kernels
    (differentiable).  No residual add — the model layer owns that.

    x      : (B, H, W, C_in) NHWC, or the block chain's lane layout: C_in
             rounded up to whole 128-lane groups with a zero tail
             (``common.in_lane_layout``)
    w_exp  : (C_in, C_mid) expand PW, or ``None`` with ``exp_act=None``
             for expansion ratio 1 (no expand convolution: the depthwise
             taps act on ``x`` itself, ``C_mid == C_in``)
    w_dw   : (k_h, k_w, C_mid) depthwise taps
    w_se1/b_se1, w_se2/b_se2 : SE squeeze/excite FCs — pass ALL FOUR as
             ``None`` for a no-SE block (MobileNet-V3's early/middle
             stages): the pass-1 pool, the host MLP and the pass-2 gate
             disappear and under ``mode="recompute"`` pass 1 is skipped
             entirely.
    w_proj : (C_mid, C_out) projection PW (linear)
    mode   : "retain" | "recompute" — pass-2 DW source (see module doc;
             ``core.autotune.get_mbconv_schedule`` picks per layer shape).
    se_act/gate_act : SE MLP nonlinearities — (silu, sigmoid) for
             EfficientNet, (relu, hard_sigmoid) for MobileNet-V3.
    residency : "resident" | "strip_dma" | "strip_dma_db" (default) — how
             the input / retained-DW streams are staged (``kernels.staging``).
    Returns (B, H', W', C_out), or (B, H', W', round_up(C_out, 128)) with
    a zero tail for an input in the lane layout.

    A call whose kernels stage its input as it is, building the whole
    conv halo in VMEM (``common.stages_in_place``: the lane layout, and
    no width cover), ticks ``kernels.staging.halo_in_vmem`` once: once per
    launch site a jitted caller traces, once per launch when called
    eagerly.
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    if stages_in_place(x):
        telemetry.counter("kernels.staging.halo_in_vmem")
    return _mbconv_fused(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                         stride=stride, padding=padding, tile_h=tile_h,
                         mode=mode, exp_act=exp_act, dw_act=dw_act,
                         se_act=se_act, gate_act=gate_act,
                         interpret=interpret, residency=residency)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "exp_act", "dw_act",
                     "se_act", "gate_act", "interpret"),
)
def convdk_mbconv_staged(
    x: jax.Array,
    w_exp: Optional[jax.Array],
    w_dw: jax.Array,
    w_se1: Optional[jax.Array],
    b_se1: Optional[jax.Array],
    w_se2: Optional[jax.Array],
    b_se2: Optional[jax.Array],
    w_proj: jax.Array,
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    exp_act: Optional[str] = "silu",
    dw_act: Optional[str] = "silu",
    se_act: Optional[str] = "silu",
    gate_act: Optional[str] = "sigmoid",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The STAGED MBConv pipeline (comparison baseline, differentiable).

    expand einsum -> HBM -> staged DW ConvDK kernel -> HBM -> SE pool +
    gate -> HBM -> projection einsum: the DW tensor round-trips through HBM
    exactly as the paper's weight-stationary baseline, which is what
    ``convdk_mbconv_fused`` eliminates.  Kept as the reference executable
    for fused-vs-staged numerics and traffic comparisons.  Takes and
    returns either layout, as the fused entry does.
    """
    from .ops import convdk_depthwise2d

    if interpret is None:
        interpret = default_interpret()
    c_in = w_dw.shape[-1] if w_exp is None else w_exp.shape[0]
    lanes = in_lane_layout(x)
    e = x[..., :c_in].astype(jnp.float32)
    if w_exp is not None:
        e = jnp.einsum("bhwc,cd->bhwd", e, w_exp.astype(jnp.float32))
    e = _act_ref(e, exp_act)
    d = convdk_depthwise2d(e, w_dw.astype(jnp.float32), stride=stride,
                           padding=padding, tile_h=tile_h,
                           interpret=interpret)
    d = _act_ref(d.astype(jnp.float32), dw_act)
    if w_se1 is not None:
        pooled = jnp.mean(d, axis=(1, 2))
        s1 = _act_ref(pooled @ w_se1.astype(jnp.float32)
                      + b_se1.astype(jnp.float32), se_act)
        gate = _act_ref(s1 @ w_se2.astype(jnp.float32)
                        + b_se2.astype(jnp.float32), gate_act)
        d = d * gate[:, None, None, :]
    out = jnp.einsum("bhwc,cd->bhwd", d, w_proj.astype(jnp.float32))
    return as_layout(out.astype(x.dtype), w_proj.shape[1], lanes)
