"""Single-pass Fused-MBConv (EfficientNet-V2) ConvDK Pallas kernel.

EfficientNet-V2's early stages replace MBConv's expand-PW + depthwise pair
with ONE dense k x k convolution (``Fused-MBConv``):

    dense k x k / s conv (C_in -> C_mid) -> act -> project 1x1 (+ residual)

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
stream, re-read once per (c_out, c_mid) block pair.

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
from .ref import _act_ref, fusedmb_ref
from .staging import StripPlan, StripStream, strip_plan


def _fusedmb_kernel(x_ref, wconv_ref, wproj_ref, o_ref, *scratch,
                    plan: StripPlan, k_h, k_w, stride, tile_h, out_w,
                    act: Optional[str]):
    """One (batch, c_out-block, row-strip, c_mid-block, c_in-block) cell.

    x_ref     : unstaged input (engine-staged per ``plan``)
    wconv_ref : (k_h, k_w, CI, CM)    dense conv block
    wproj_ref : (CM, CO)              projection block
    o_ref     : (1, tile_h, out_w, CO)
    scratch   : conv accumulator (tile_h, out_w, CM) f32 carrying partial
                dense-conv sums across the c_in grid dim, projection
                accumulator (tile_h, out_w, CO) f32 carrying partial sums
                across the c_mid grid dim, then the staging engine's refs.
    """
    s = stride
    stage_refs, (conv_ref, proj_ref) = plan.take_scratch(scratch)
    cm = pl.program_id(3)
    ci = pl.program_id(4)
    n_cm = pl.num_programs(3)
    n_ci = pl.num_programs(4)
    win = StripStream(plan, x_ref, stage_refs).get()

    # Dense-conv tap loop: each tap contracts the strided window slice
    # (tile_h, out_w, CI) with its (CI, CM) weight plane — the expand-PW
    # and DW of a classic MBConv, collapsed into one MXU contraction per
    # tap.  Summed over taps here, over c_in blocks via conv_ref.
    part = jnp.zeros((tile_h, out_w, wconv_ref.shape[-1]), jnp.float32)
    for j in range(k_h):
        for i in range(k_w):
            xs = win.read(j, i, tile_h, out_w, s)
            part = part + jax.lax.dot_general(
                xs.reshape(tile_h * out_w, xs.shape[-1]).astype(jnp.float32),
                wconv_ref[j, i].astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(tile_h, out_w, -1)

    @pl.when(ci == 0)
    def _init():
        conv_ref[...] = part

    @pl.when(ci > 0)
    def _accumulate():
        conv_ref[...] = conv_ref[...] + part

    @pl.when(ci == n_ci - 1)
    def _project():
        e = _act_ref(conv_ref[...], act)
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
            o_ref[0] = proj_ref[...].astype(o_ref.dtype)


def fusedmb_pallas(x_pad, w_conv, w_proj, *, stride, out_w, tile_h, n_th,
                   ci_block, cm_block, co_block, act, interpret,
                   residency=DEFAULT_RESIDENCY):
    """Raw single-pass launch over a pre-padded input.

    x_pad  : (B, H_tot, W_pad, CI_pad)
    w_conv : (k_h, k_w, CI_pad, CM_pad) HWIO
    w_proj : (CM_pad, CO_pad)
    returns (B, n_th*tile_h, out_w, CO_pad)
    """
    b, h_tot, w_pad, ci_pad = x_pad.shape
    k_h, k_w, _, cm_pad = w_conv.shape
    co_pad = w_proj.shape[1]
    grid = (b, co_pad // co_block, n_th, cm_pad // cm_block,
            ci_pad // ci_block)

    plan = strip_plan(
        h_tot=h_tot, w_tot=w_pad, c_block=ci_block, tile_h=tile_h,
        grid=grid, window_dims=(0, 2, 4), stride=stride, k_h=k_h,
        residency=residency)
    kernel = functools.partial(
        _fusedmb_kernel, plan=plan, k_h=k_h, k_w=k_w, stride=stride,
        tile_h=tile_h, out_w=out_w, act=act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            plan.in_spec(lambda bi, co, ti, cm, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((k_h, k_w, ci_block, cm_block),
                         lambda bi, co, ti, cm, ci: (0, 0, ci, cm)),
            pl.BlockSpec((cm_block, co_block),
                         lambda bi, co, ti, cm, ci: (cm, co)),
        ],
        out_specs=pl.BlockSpec(
            (1, tile_h, out_w, co_block),
            lambda bi, co, ti, cm, ci: (bi, ti, 0, co)),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_th * tile_h, out_w, co_pad), x_pad.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_h, out_w, cm_block), jnp.float32),
            pltpu.VMEM((tile_h, out_w, co_block), jnp.float32),
            *plan.scratch_shapes(x_pad.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="fusedmb",
    )(x_pad, w_conv, w_proj)


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
    """
    validate_collective(collective)
    b, h, w_in, c_in = x.shape
    k_h, k_w, ci_w, c_mid = w_conv.shape
    assert ci_w == c_in, (w_conv.shape, c_in)
    c_out = w_proj.shape[1]
    assert w_proj.shape[0] == c_mid, (w_proj.shape, c_mid)
    s = stride
    geo = launch_geometry(h, w_in, k_h, k_w, s, padding, tile_h)
    out_h, out_w = geo.out_h, geo.out_w

    ci_block = pick_channel_block(c_in)
    ci_pad = _round_up(c_in, ci_block)
    cm_block = pick_channel_block(c_mid)
    cm_pad = _round_up(c_mid, cm_block)
    co_block = min(128, _round_up(c_out, 8))
    co_pad = _round_up(c_out, co_block)

    xp = jnp.pad(x, (*geo.pads, (0, ci_pad - c_in)))
    wconv_p = jnp.pad(w_conv, ((0, 0), (0, 0), (0, ci_pad - c_in),
                               (0, cm_pad - c_mid)))
    wproj_p = jnp.pad(w_proj, ((0, cm_pad - c_mid), (0, co_pad - c_out)))

    out = fusedmb_pallas(
        xp, wconv_p, wproj_p, stride=s, out_w=geo.out_wk, tile_h=geo.tile_h,
        n_th=geo.n_th, ci_block=ci_block, cm_block=cm_block,
        co_block=co_block, act=act, interpret=interpret, residency=residency)
    if axis_name is not None and collective == "psum_scatter":
        # layout-aware exit, same contract as MBConv pass 2: zero w_proj
        # columns pad a non-dividing c_out to ``scatter_width`` (their
        # partials are exactly zero), the wrapper slices them back.
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
    # VJP is exact (same pattern as convdk_fused / convdk_mbconv).
    x, w_conv, w_proj = res
    _, vjp = jax.vjp(
        lambda x_, wc_, wp_: fusedmb_ref(
            x_, wc_, wp_, stride=stride, padding=padding, act=act),
        x, w_conv, w_proj,
    )
    return vjp(g)


_fusedmb_op.defvjp(_fusedmb_fwd, _fusedmb_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "act", "interpret",
                     "residency"),
)
def convdk_fusedmb_fused(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: jax.Array,
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

    x      : (B, H, W, C_in) NHWC
    w_conv : (k_h, k_w, C_in, C_mid) HWIO dense conv (the collapsed
             expand+DW of EfficientNet-V2's fused stages)
    w_proj : (C_mid, C_out) projection PW (linear)
    act    : conv activation (EfficientNet-V2 uses silu)
    residency : "resident" | "strip_dma" | "strip_dma_db" (default) — how
             the input stream is staged (``kernels.staging``).
    Returns (B, H', W', C_out).  The expanded (C_mid) tensor never touches
    HBM; there is no SE stage and no second pass.
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    return _fusedmb_op(x, w_conv, w_proj, stride, padding, tile_h, act,
                       interpret, residency)


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "act", "interpret"),
)
def convdk_fusedmb_staged(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: jax.Array,
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
    strip structure.
    """
    del tile_h
    if interpret is None:
        interpret = default_interpret()
    e = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w_conv.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    e = _act_ref(e, act)
    out = jnp.einsum("bhwc,cd->bhwd", e, w_proj.astype(jnp.float32))
    return out.astype(x.dtype)
