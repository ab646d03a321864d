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

Blocks with expansion ratio 1 (EfficientNet's MBConv1) pass the identity as
``w_exp`` with ``exp_act=None`` — the kernel math is unchanged and exact.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.perfmodel import (
    DEFAULT_COLLECTIVE,
    DEFAULT_RESIDENCY,
    pick_channel_block,
    validate_collective,
)
from .common import (
    compiler_params,
    default_interpret,
    launch_geometry,
    round_up as _round_up,
)
from .ref import _act_ref, mbconv_ref
from .staging import StripPlan, StripStream, strided_read, strip_plan


def _expanded_taps(acc_ref, w_dw_ref, *, exp_act, dw_act, k_h, k_w, stride,
                   tile_h, out_w):
    """Algorithm-2 tap loop over the finished expand accumulator.

    acc_ref: (in_rows, w_tot, CM) f32 -> (tile_h, out_w, CM) f32.  Each tap
    is one (strided, for s = 2) load from the ref — Mosaic cannot
    stride-slice a loaded value — so the expand activation is applied to
    the ref IN PLACE first.
    """
    if exp_act is not None:
        acc_ref[...] = _act_ref(acc_ref[...], exp_act)
    dw = jnp.zeros((tile_h, out_w, acc_ref.shape[-1]), jnp.float32)
    for j in range(k_h):
        for i in range(k_w):
            xs = strided_read(acc_ref, (), j, i, tile_h, out_w, stride)
            dw = dw + xs * w_dw_ref[j, i].astype(jnp.float32)
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


def _mbconv_pass1_kernel(x_ref, wexp_ref, wdw_ref, *rest,
                         plan: StripPlan, k_h, k_w, stride, tile_h, out_w,
                         out_h, valid_w, exp_act: Optional[str],
                         dw_act: Optional[str], se: bool, retain: bool):
    """One (batch, c_mid-block, row-strip, c_in-block) grid cell of pass 1.

    x_ref    : unstaged input (engine-staged per ``plan``)
    wexp_ref : (CI, CM)               expand-PW block
    wdw_ref  : (k_h, k_w, CM)         depthwise taps
    rest     : (pool_ref,) if se — the (1, 1, CM) on-chip SE pool
               accumulator (sums) — then (dw_out_ref,) if retain, then
               acc_ref + staging refs.  An se=off launch carries NO pool
               output at all: the no-SE block pays zero pool VMEM/HBM.
    """
    rest = tuple(rest)
    if se:
        pool_ref, *rest = rest
    if retain:
        dwo_ref, *rest = rest
    stage_refs, (acc_ref,) = plan.take_scratch(tuple(rest))
    ti = pl.program_id(2)
    ci = pl.program_id(3)
    n_ci = pl.num_programs(3)
    win = StripStream(plan, x_ref, stage_refs).get()
    _expand_accumulate(win.read(), wexp_ref, acc_ref, ci=ci)

    @pl.when(ci == n_ci - 1)
    def _finish_strip():
        dw = _expanded_taps(acc_ref, wdw_ref, exp_act=exp_act, dw_act=dw_act,
                            k_h=k_h, k_w=k_w, stride=stride, tile_h=tile_h,
                            out_w=out_w)
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


def _mbconv_pass2_recompute_kernel(x_ref, wexp_ref, wdw_ref, *rest,
                                   plan: StripPlan, k_h, k_w, stride,
                                   tile_h, out_w, exp_act: Optional[str],
                                   dw_act: Optional[str], se: bool):
    """One (batch, c_out-block, row-strip, c_mid-block, c_in-block) cell.

    Recomputes expand+DW exactly as pass 1 (the DW tensor never existed in
    HBM), multiplies by the SE gate (when ``se`` — an se=off launch carries
    no scale input at all) and contracts with the projection block —
    partial projection sums carried across the c_mid grid dimension.
    """
    rest = tuple(rest)
    if se:
        scale_ref, *rest = rest
    wproj_ref, o_ref, *scratch = rest
    stage_refs, (acc_ref, proj_ref) = plan.take_scratch(tuple(scratch))
    cm = pl.program_id(3)
    ci = pl.program_id(4)
    n_cm = pl.num_programs(3)
    n_ci = pl.num_programs(4)
    win = StripStream(plan, x_ref, stage_refs).get()
    _expand_accumulate(win.read(), wexp_ref, acc_ref, ci=ci)

    @pl.when(ci == n_ci - 1)
    def _project():
        dw = _expanded_taps(acc_ref, wdw_ref, exp_act=exp_act, dw_act=dw_act,
                            k_h=k_h, k_w=k_w, stride=stride, tile_h=tile_h,
                            out_w=out_w)
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
            o_ref[0] = proj_ref[...].astype(o_ref.dtype)


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
        o_ref[0] = proj_ref[...].astype(o_ref.dtype)


def mbconv_pass1_pallas(x_pad, w_exp, w_dw, *, stride, out_w, out_h, valid_w,
                        tile_h, n_th, ci_block, cm_block, exp_act, dw_act,
                        retain, interpret, se=True,
                        residency=DEFAULT_RESIDENCY):
    """Raw pass-1 launch: (pool_sums-or-None, dw_retained-or-None).

    ``out_w`` is the launched (sublane-covered) output width; the pool
    counts only the first ``valid_w`` columns and ``out_h`` rows.

    ``se=False`` drops the pool output (and its VMEM accumulator) from the
    launch entirely — an se=off retain pass writes only the DW tensor.
    """
    assert se or retain, "se=off + recompute has no pass 1 at all"
    b, h_tot, w_pad, ci_pad = x_pad.shape
    k_h, k_w, cm_pad = w_dw.shape
    grid = (b, cm_pad // cm_block, n_th, ci_pad // ci_block)

    plan = strip_plan(
        h_tot=h_tot, w_tot=w_pad, c_block=ci_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 2, 3), stride=stride, k_h=k_h,
        residency=residency)
    kernel = functools.partial(
        _mbconv_pass1_kernel, plan=plan, k_h=k_h, k_w=k_w, stride=stride,
        tile_h=tile_h, out_w=out_w, out_h=out_h, valid_w=valid_w,
        exp_act=exp_act,
        dw_act=dw_act, se=se, retain=retain)
    out_shape = []
    out_specs = []
    if se:
        out_shape.append(jax.ShapeDtypeStruct((b, 1, cm_pad), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, cm_block),
                                      lambda bi, cm, ti, ci: (bi, 0, cm)))
    if retain:
        out_shape.append(jax.ShapeDtypeStruct(
            (b, n_th * tile_h, out_w, cm_pad), x_pad.dtype))
        out_specs.append(pl.BlockSpec(
            (1, tile_h, out_w, cm_block),
            lambda bi, cm, ti, ci: (bi, ti, 0, cm)))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            plan.in_spec(lambda bi, cm, ti, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((ci_block, cm_block),
                         lambda bi, cm, ti, ci: (ci, cm)),
            pl.BlockSpec((k_h, k_w, cm_block),
                         lambda bi, cm, ti, ci: (0, 0, cm)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((plan.in_rows, w_pad, cm_block), jnp.float32),
            *plan.scratch_shapes(x_pad.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="mbconv_pass1",
    )(x_pad, w_exp, w_dw)
    outs = list(outs)
    pool = outs.pop(0) if se else None
    dw_ret = outs.pop(0) if retain else None
    return pool, dw_ret


def mbconv_pass2_recompute_pallas(x_pad, w_exp, w_dw, scale, w_proj, *,
                                  stride, out_w, tile_h, n_th, ci_block,
                                  cm_block, co_block, exp_act, dw_act,
                                  interpret, residency=DEFAULT_RESIDENCY):
    """``scale=None`` launches the se=off variant: no gate input, no gate
    multiply — the no-SE block pays zero scale bytes."""
    se = scale is not None
    b, h_tot, w_pad, ci_pad = x_pad.shape
    k_h, k_w, cm_pad = w_dw.shape
    co_pad = w_proj.shape[1]
    grid = (b, co_pad // co_block, n_th, cm_pad // cm_block,
            ci_pad // ci_block)

    plan = strip_plan(
        h_tot=h_tot, w_tot=w_pad, c_block=ci_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 2, 4), stride=stride, k_h=k_h,
        residency=residency)
    kernel = functools.partial(
        _mbconv_pass2_recompute_kernel, plan=plan, k_h=k_h, k_w=k_w,
        stride=stride, tile_h=tile_h, out_w=out_w, exp_act=exp_act,
        dw_act=dw_act, se=se)
    in_specs = [
        plan.in_spec(lambda bi, co, ti, cm, ci: (bi, 0, 0, ci)),
        pl.BlockSpec((ci_block, cm_block),
                     lambda bi, co, ti, cm, ci: (ci, cm)),
        pl.BlockSpec((k_h, k_w, cm_block),
                     lambda bi, co, ti, cm, ci: (0, 0, cm)),
    ]
    operands = [x_pad, w_exp, w_dw]
    if se:
        in_specs.append(pl.BlockSpec((1, 1, cm_block),
                                     lambda bi, co, ti, cm, ci: (bi, 0, cm)))
        operands.append(scale)
    in_specs.append(pl.BlockSpec((cm_block, co_block),
                                 lambda bi, co, ti, cm, ci: (cm, co)))
    operands.append(w_proj)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tile_h, out_w, co_block),
            lambda bi, co, ti, cm, ci: (bi, ti, 0, co)),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_th * tile_h, out_w, co_pad), x_pad.dtype),
        scratch_shapes=[
            pltpu.VMEM((plan.in_rows, w_pad, cm_block), jnp.float32),
            pltpu.VMEM((tile_h, out_w, co_block), jnp.float32),
            *plan.scratch_shapes(x_pad.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="mbconv_pass2_recompute",
    )(*operands)


def mbconv_pass2_retain_pallas(dw_ret, scale, w_proj, *, out_w, tile_h,
                               n_th, cm_block, co_block, interpret,
                               residency=DEFAULT_RESIDENCY):
    b = dw_ret.shape[0]
    cm_pad = dw_ret.shape[-1]
    co_pad = w_proj.shape[1]
    grid = (b, co_pad // co_block, n_th, cm_pad // cm_block)

    # The retained-DW re-read: non-overlapping tile_h-row blocks (k_h=1,
    # stride=1 geometry) — the double-buffered DMA stream of the tentpole.
    se = scale is not None
    plan = strip_plan(
        h_tot=dw_ret.shape[1], w_tot=out_w, c_block=cm_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 2, 3), residency=residency)
    kernel = functools.partial(_mbconv_pass2_retain_kernel, plan=plan,
                               tile_h=tile_h, out_w=out_w, se=se)
    in_specs = [plan.in_spec(lambda bi, co, ti, cm: (bi, ti, 0, cm))]
    operands = [dw_ret]
    if se:
        in_specs.append(pl.BlockSpec((1, 1, cm_block),
                                     lambda bi, co, ti, cm: (bi, 0, cm)))
        operands.append(scale)
    in_specs.append(pl.BlockSpec((cm_block, co_block),
                                 lambda bi, co, ti, cm: (cm, co)))
    operands.append(w_proj)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tile_h, out_w, co_block),
            lambda bi, co, ti, cm: (bi, ti, 0, co)),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_th * tile_h, out_w, co_pad), dw_ret.dtype),
        scratch_shapes=[pltpu.VMEM((tile_h, out_w, co_block), jnp.float32),
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
    """
    validate_collective(collective)
    se = w_se1 is not None
    b, h, w_in, c_in = x.shape
    k_h, k_w, c_mid = w_dw.shape
    assert w_exp.shape == (c_in, c_mid), (w_exp.shape, c_in, c_mid)
    c_out = w_proj.shape[1]
    assert w_proj.shape[0] == c_mid, (w_proj.shape, c_mid)
    assert mode in ("retain", "recompute"), mode
    s = stride

    geo = launch_geometry(h, w_in, k_h, k_w, s, padding, tile_h)
    out_h, out_w, tile_h, n_th = geo.out_h, geo.out_w, geo.tile_h, geo.n_th

    ci_block = pick_channel_block(c_in)
    ci_pad = _round_up(c_in, ci_block)
    cm_block = pick_channel_block(c_mid)
    cm_pad = _round_up(c_mid, cm_block)
    co_block = min(128, _round_up(c_out, 8))
    co_pad = _round_up(c_out, co_block)

    xp = jnp.pad(x, (*geo.pads, (0, ci_pad - c_in)))
    wexp_p = jnp.pad(w_exp, ((0, ci_pad - c_in), (0, cm_pad - c_mid)))
    wdw_p = jnp.pad(w_dw, ((0, 0), (0, 0), (0, cm_pad - c_mid)))
    wproj_p = jnp.pad(w_proj, ((0, cm_pad - c_mid), (0, co_pad - c_out)))

    if se or mode == "retain":
        pool, dw_ret = mbconv_pass1_pallas(
            xp, wexp_p, wdw_p, stride=s, out_w=geo.out_wk, out_h=out_h,
            valid_w=out_w, tile_h=tile_h, n_th=n_th, ci_block=ci_block,
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
            dw_ret, scale, wproj_p, out_w=geo.out_wk, tile_h=tile_h,
            n_th=n_th, cm_block=cm_block, co_block=co_block,
            interpret=interpret, residency=residency)
    else:
        out = mbconv_pass2_recompute_pallas(
            xp, wexp_p, wdw_p, scale, wproj_p, stride=s, out_w=geo.out_wk,
            tile_h=tile_h, n_th=n_th, ci_block=ci_block, cm_block=cm_block,
            co_block=co_block, exp_act=exp_act, dw_act=dw_act,
            interpret=interpret, residency=residency)
    if axis_name is not None and collective == "psum_scatter":
        # reduce-scatter over the channel dim: (mp-1)/mp words per
        # reduced word instead of the ring's 2*(mp-1)/mp, and this
        # shard keeps only its channel slice — the layout-aware exit.
        # Non-dividing c_out scatters at ``scatter_width`` (the next
        # model-factor multiple): the extra columns are zero w_proj
        # columns, so their partials are exactly zero and the wrapper
        # slices them back off the gathered-global view.
        cw = scatter_width if scatter_width else c_out
        out = out[:, :out_h, :out_w, :min(cw, out.shape[-1])]
        if out.shape[-1] < cw:
            out = jnp.pad(
                out, ((0, 0), (0, 0), (0, 0), (0, cw - out.shape[-1])))
        out = jax.lax.psum_scatter(out, axis_name,
                                   scatter_dimension=3, tiled=True)
    else:
        out = out[:, :out_h, :out_w, :c_out]
        if axis_name is not None:
            # projection partials: each shard contracted only its c_mid
            # slice
            out = jax.lax.psum(out, axis_name)
    return out


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
    # stage for w_se1=None, matching the se=off kernel path; the SE-param
    # cotangents come back as None there, as custom_vjp expects.
    _, vjp = jax.vjp(
        lambda *p: mbconv_ref(*p, stride=stride, padding=padding,
                              exp_act=exp_act, dw_act=dw_act,
                              se_act=se_act, gate_act=gate_act),
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
def convdk_mbconv_fused(
    x: jax.Array,
    w_exp: jax.Array,
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

    x      : (B, H, W, C_in) NHWC
    w_exp  : (C_in, C_mid) expand PW (identity + ``exp_act=None`` for
             expansion ratio 1)
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
    Returns (B, H', W', C_out).
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    return _mbconv_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                      stride, padding, tile_h, mode, exp_act, dw_act,
                      interpret, residency, se_act, gate_act)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "exp_act", "dw_act",
                     "se_act", "gate_act", "interpret"),
)
def convdk_mbconv_staged(
    x: jax.Array,
    w_exp: jax.Array,
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
    for fused-vs-staged numerics and traffic comparisons.
    """
    from .ops import convdk_depthwise2d

    if interpret is None:
        interpret = default_interpret()
    e = jnp.einsum("bhwc,cd->bhwd", x.astype(jnp.float32),
                   w_exp.astype(jnp.float32))
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
    return out.astype(x.dtype)
