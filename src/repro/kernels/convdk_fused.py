"""Fused depthwise-separable ConvDK Pallas kernel (DW + PW in one pass).

The staged pipeline (``ops.convdk_depthwise2d`` + a host-side 1x1 matmul)
round-trips through HBM twice per separable block:

1. ``ops.stage_row_strips`` materializes a *duplicated, overlapping* copy of
   the input (the halo rows of every strip are written twice), and
2. the depthwise output is written back to HBM only to be re-read by the
   pointwise (1x1) projection.

Both trips are exactly the IB<->TRF buffer traffic Algorithms 1-2 of the
paper are designed to eliminate.  This kernel removes them:

* **Strip staging through the shared engine** (``kernels.staging``) — the
  kernel receives the *unstaged* ``(B, H_tot, W_pad, C)`` input and stages
  each grid cell's overlapping ``(tile_h-1)*s + k_h`` row window per the
  schedule's **residency**: a VMEM-resident ``pl.ds`` slice
  (``"resident"``), a per-cell async DMA from the ``ANY``/HBM space
  (``"strip_dma"``), or a double-buffered DMA stream that prefetches the
  next cell's window while this one computes (``"strip_dma_db"``, the
  production default).  Halo rows are re-read, never re-written to HBM
  (the TRF-residency property of Algorithm 1's shift cycles).
* **Fused pointwise projection** — the DW accumulator is contracted with the
  ``(C_in, C_out)`` pointwise weight on the lane axis while still in VMEM.
  Depthwise outputs never touch HBM at all; the only HBM write is the final
  block output.

Grid layout: ``(batch, row_strip, c_out_block, c_in_block)`` with the input
-channel reduction innermost so the f32 scratch accumulator carries partial
PW sums across sequential grid steps (the standard Pallas reduction-dim
pattern).  Because DW is depthwise, its per-``c_in``-block accumulator is
complete before the PW contraction of that block — so a DW-stage activation
(the BN-free stand-in for MobileNet's ReLU6 between DW and PW) can be fused
exactly.

Interpret mode (the CI backend) executes the SAME DMA-structured code path
— the pallas interpreter implements the copy/semaphore primitives — so the
parity suite exercises the production staging structure, not a CI-only
twin.  The traffic model for schedule selection lives in ``core.perfmodel``
/ ``core.autotune`` and prices every residency.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.perfmodel import DEFAULT_RESIDENCY, pick_channel_block
from .common import (
    compiler_params,
    default_interpret,
    launch_geometry,
    round_up as _round_up,
)
from .ref import _act_ref, separable_ref
from .staging import StripPlan, StripStream, strip_plan


def _fused_kernel(x_ref, wdw_ref, wpw_ref, o_ref, *scratch, plan: StripPlan,
                  k_h: int, k_w: int, stride: int, tile_h: int, out_w: int,
                  dw_act: Optional[str], act: Optional[str]):
    """One (batch, row-strip, c_out-block, c_in-block) grid cell.

    x_ref   : unstaged input — a full-height VMEM channel block
              (``resident``) or the whole ``ANY``-space tensor (DMA modes)
    wdw_ref : (k_h, k_w, CI)         depthwise taps (the "TM")
    wpw_ref : (CI, CO)               pointwise projection block
    o_ref   : (1, tile_h, out_w, CO)
    scratch : (tile_h, out_w, CO) f32 PW accumulator (partial sums across
              the innermost c_in grid dim) + the staging engine's refs.
    """
    s = stride
    stage_refs, (acc_ref,) = plan.take_scratch(scratch)
    ci = pl.program_id(3)
    n_ci = pl.num_programs(3)

    # The staged strip window: (in_rows, w_tot, CI).  Under strip_dma_db
    # this wait also kicks off the prefetch of the NEXT cell's window.
    win = StripStream(plan, x_ref, stage_refs).get()

    # Algorithm-2 tap loop: l shift cycles x k_h row taps over the resident
    # strip, all width blocks updated per tap; a stride-s tap is one
    # strided VMEM load (see convdk_dw._dw2d_kernel).
    dw = jnp.zeros((tile_h, out_w, wdw_ref.shape[-1]), jnp.float32)
    for j in range(k_h):
        for i in range(k_w):
            xs = win.read(j, i, tile_h, out_w, s)
            dw = dw + xs.astype(jnp.float32) * wdw_ref[j, i].astype(jnp.float32)

    # Depthwise is per-channel, so this block's DW output is final: the
    # mid-block activation fuses exactly, before the lane-axis contraction.
    dw = _act_ref(dw, dw_act)

    # Fused pointwise: consume the DW accumulator while it is still in VMEM.
    partial = jax.lax.dot_general(
        dw.reshape(tile_h * out_w, dw.shape[-1]),
        wpw_ref[:, :].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(tile_h, out_w, -1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = partial

    @pl.when(ci > 0)
    def _accumulate():
        acc_ref[...] = acc_ref[...] + partial

    @pl.when(ci == n_ci - 1)
    def _finalize():
        o_ref[0] = _act_ref(acc_ref[...], act).astype(o_ref.dtype)


def fused_separable_pallas(
    x_pad: jax.Array,
    w_dw: jax.Array,
    w_pw: jax.Array,
    *,
    stride: int,
    out_w: int,
    tile_h: int,
    n_th: int,
    ci_block: int,
    co_block: int,
    dw_act: Optional[str] = None,
    act: Optional[str] = None,
    interpret: bool = False,
    residency: str = DEFAULT_RESIDENCY,
) -> jax.Array:
    """Raw fused kernel launch over a pre-padded input.

    x_pad : (B, H_tot, W_pad, C_in) with H_tot >= (n_th-1)*tile_h*s + in_rows
    w_dw  : (k_h, k_w, C_in);  w_pw : (C_in, C_out)
    returns (B, n_th*tile_h, out_w, C_out)
    """
    b, h_tot, w_pad, c_in = x_pad.shape
    k_h, k_w, _ = w_dw.shape
    c_out = w_pw.shape[1]
    assert c_in % ci_block == 0, (c_in, ci_block)
    assert c_out % co_block == 0, (c_out, co_block)
    grid = (b, n_th, c_out // co_block, c_in // ci_block)

    plan = strip_plan(
        h_tot=h_tot, w_tot=w_pad, c_block=ci_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 1, 3), stride=stride, k_h=k_h,
        residency=residency)

    kernel = functools.partial(
        _fused_kernel, plan=plan, k_h=k_h, k_w=k_w, stride=stride,
        tile_h=tile_h, out_w=out_w, dw_act=dw_act, act=act,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            plan.in_spec(lambda bi, ti, co, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((k_h, k_w, ci_block),
                         lambda bi, ti, co, ci: (0, 0, ci)),
            pl.BlockSpec((ci_block, co_block),
                         lambda bi, ti, co, ci: (ci, co)),
        ],
        out_specs=pl.BlockSpec(
            (1, tile_h, out_w, co_block),
            lambda bi, ti, co, ci: (bi, ti, 0, co),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_th * tile_h, out_w, c_out), x_pad.dtype),
        scratch_shapes=[pltpu.VMEM((tile_h, out_w, co_block), jnp.float32),
                        *plan.scratch_shapes(x_pad.dtype)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="fused_separable",
    )(x_pad, w_dw, w_pw)


def _fused_impl(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act,
                interpret, residency=DEFAULT_RESIDENCY):
    b, h, w_in, c = x.shape
    k_h, k_w, cw = w_dw.shape
    c_in_pw, c_out = w_pw.shape
    assert cw == c and c_in_pw == c, (cw, c_in_pw, c)
    s = stride
    geo = launch_geometry(h, w_in, k_h, k_w, s, padding, tile_h)

    # input channels: 128-lane blocks of the lane-padded width (what Mosaic
    # tiles and the strip DMA can address); output channels: plain
    # 128-lane cap — padding c_out only spends zero-lane MACs and SHRINKS
    # n_co (fewer input re-reads).
    ci_block = pick_channel_block(c)
    ci_pad = _round_up(c, ci_block)
    co_block = min(128, _round_up(c_out, 8))
    co_pad = _round_up(c_out, co_block)
    xp = jnp.pad(x, (*geo.pads, (0, ci_pad - c)))
    wdp = jnp.pad(w_dw, ((0, 0), (0, 0), (0, ci_pad - c)))
    wpp = jnp.pad(w_pw, ((0, ci_pad - c), (0, co_pad - c_out)))

    out = fused_separable_pallas(
        xp, wdp, wpp, stride=s, out_w=geo.out_wk, tile_h=geo.tile_h,
        n_th=geo.n_th, ci_block=ci_block, co_block=co_block, dw_act=dw_act,
        act=act, interpret=interpret, residency=residency,
    )
    return out[:, :geo.out_h, :geo.out_w, :c_out]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fused_op(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act, interpret,
              residency):
    return _fused_impl(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act,
                       interpret, residency)


def _fused_fwd(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act, interpret,
               residency):
    out = _fused_op(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act,
                    interpret, residency)
    return out, (x, w_dw, w_pw)


def _fused_bwd(stride, padding, tile_h, dw_act, act, interpret, residency,
               res, g):
    # Backward through the mathematically identical reference composition —
    # the kernel computes the same separable block, so the VJP is exact.
    x, w_dw, w_pw = res
    _, vjp = jax.vjp(
        lambda x_, wd_, wp_: separable_ref(
            x_, wd_, wp_, stride=stride, padding=padding, dw_act=dw_act,
            act=act),
        x, w_dw, w_pw,
    )
    return vjp(g)


_fused_op.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "dw_act", "act",
                     "interpret", "residency"),
)
def convdk_fused_separable(
    x: jax.Array,
    w_dw: jax.Array,
    w_pw: jax.Array,
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    dw_act: Optional[str] = None,
    act: Optional[str] = None,
    interpret: Optional[bool] = None,
    residency: Optional[str] = None,
) -> jax.Array:
    """Fused depthwise-separable block via one ConvDK Pallas kernel
    (differentiable).

    Computes ``act(pointwise(dw_act(depthwise(x, w_dw)), w_pw))`` with a
    single HBM read of ``x`` and a single HBM write of the block output.

    x    : (B, H, W, C_in) NHWC
    w_dw : (k_h, k_w, C_in) depthwise taps
    w_pw : (C_in, C_out) pointwise projection
    dw_act / act : None | "relu" | "relu6", fused mid-block / output
    activations.
    residency : "resident" | "strip_dma" | "strip_dma_db" (default) — how
    the input stream is staged (see ``kernels.staging``); the autotuner's
    per-layer pick routes through ``models.common.separable_block``.
    Returns (B, H', W', C_out).
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    return _fused_op(x, w_dw, w_pw, stride, padding, tile_h, dw_act, act,
                     interpret, residency)
