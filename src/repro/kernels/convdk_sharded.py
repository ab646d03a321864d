"""Mesh-native wrappers for the fused ConvDK pipelines (``shard_map``).

The fused kernels in ``convdk_fused`` / ``convdk_mbconv`` keep the
depthwise tensor out of HBM on ONE core; at production scale the
batch/channel grid does not fit a single core, and the paper's traffic
claim must survive partitioning (the Eyeriss/MAERI lesson: reuse arguments
re-prove, they do not transfer).  This module wraps both pipelines in
``shard_map`` over the repo's ("data", "model") mesh
(``repro.sharding`` / ``launch.mesh``), with the axis mapping:

* **batch -> "data"** for both families — pure data parallelism, every
  device runs the identical fused schedule on its batch slice.  A "pod"
  axis (multi-pod meshes) joins it as an outer multiplier: batch shards
  over ("pod", "data") jointly, no new collective;
* **separable: c_out -> "model"** — the kernel grid's channel axis.  The
  PW contraction reduces over c_in, which stays replicated, so each
  device's output-channel slice is complete on-chip and the sharded path
  needs NO collective;
* **MBConv: c_mid -> "model"** — the expanded/DW/SE width (the kernel
  grid's channel axis).  Expand columns, DW taps, the retained DW tensor
  and the excite FC are all local to the shard, but the two contractions
  over the full C_mid become cross-device reductions inside
  ``_mbconv_impl``: the pass-1 SE pool leaves the chip once as a tiny
  (B, C_se) squeeze ``psum``, and pass 2 reduces the projection partials
  per the schedule's **collective** axis — ``psum`` (ring all-reduce,
  replicated output) or ``psum_scatter`` (half the wire words, output
  sharded on c_out for a layout-aware consumer).

Each shard runs the shared strip-staging engine (``kernels.staging``)
under the schedule's residency, so the DMA-structured input streams are
identical on and off the mesh.

Both wrappers are differentiable with the same pattern as their
single-device counterparts: the VJP runs through the mathematically
identical reference composition on the full (replicated) tensors.

**Serving-rate call sites**: the public wrappers dispatch through a
process-wide cache of ``jax.jit``-ted entry points keyed on (mesh, static
schedule) — without it every eager call rebuilt the ``shard_map`` closure
and re-traced the whole fused pipeline (the ROADMAP re-trace edge).
``TRACE_COUNTS`` records actual impl traces per family so the regression
test can pin the cache down.

Per-device HBM traffic and the psum bytes are priced by
``core.perfmodel.sharded_separable_traffic`` /
``sharded_mbconv_traffic``; ``core.autotune`` solves schedules under
``mesh_shape`` and ``residency`` axes so partitionings never collide.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import (
    residual_barrier,
    residual_barrier_needed,
    shard_map_compat,
)
from ..core import telemetry
from ..core.perfmodel import (
    DEFAULT_COLLECTIVE,
    DEFAULT_LAYOUT,
    DEFAULT_RESIDENCY,
    scatter_c_out,
    validate_layout,
)
from .common import as_layout, default_interpret, in_lane_layout
from .convdk_fused import _fused_impl
from .convdk_fusedmb import _fusedmb_impl, taps_per_pass
from .convdk_mbconv import _mbconv_impl
from .ref import _act_ref, fusedmb_ref, mbconv_ref, separable_ref

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"

# Times each sharded impl body was TRACED (not called) — a jit-cache hit
# leaves these untouched.  tests/test_distributed_fused.py pins the
# serving-rate contract: N calls at one (mesh, schedule, shapes) == 1 trace.
TRACE_COUNTS: Dict[str, int] = {"separable": 0, "mbconv": 0, "fusedmb": 0}


def conv_mesh_shape(mesh) -> Tuple[int, int]:
    """Effective (data, model) factors of a mesh (1 for an absent axis).

    A "pod" axis (multi-pod deployments, ``launch.mesh`` multi_pod=True)
    folds into the data factor as a PURE data-parallel outer multiplier:
    batch shards over ("pod", "data") jointly, no new collective appears
    (the MBConv reductions stay inside each model group), and the pricing
    is a per-pod replica of the existing totals — which is exactly what
    ``perfmodel`` computes from the folded dp."""
    return (mesh.shape.get(POD_AXIS, 1) * mesh.shape.get(DATA_AXIS, 1),
            mesh.shape.get(MODEL_AXIS, 1))


def _batch_axes(mesh):
    """The PartitionSpec entry the batch dim shards over: ("pod", "data")
    jointly when the mesh carries a pod axis, plain "data" otherwise."""
    return (POD_AXIS, DATA_AXIS) if POD_AXIS in mesh.shape else DATA_AXIS


def can_shard_fused(mesh, batch: int, channels: int) -> bool:
    """True iff the data/model axes exist and the EFFECTIVE factors
    (pod folded into data) divide (batch, channel grid) — the model-layer
    routing falls back to the single-device kernel otherwise (same drop
    policy as ``sharding.spec_for``)."""
    if DATA_AXIS not in mesh.shape or MODEL_AXIS not in mesh.shape:
        return False
    dp, mp = conv_mesh_shape(mesh)
    return batch % dp == 0 and channels % mp == 0


def _require_shardable(mesh, batch: int, channels: int, channel_name: str):
    if DATA_AXIS not in mesh.shape or MODEL_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh must carry '{DATA_AXIS}' and '{MODEL_AXIS}' axes, got "
            f"{dict(mesh.shape)}")
    dp, mp = conv_mesh_shape(mesh)
    if batch % dp != 0:
        raise ValueError(
            f"batch {batch} not divisible by the effective data factor "
            f"{dp} (pod*data)")
    if channels % mp != 0:
        raise ValueError(
            f"{channel_name} {channels} not divisible by {MODEL_AXIS}={mp}")


# ---------------------------------------------------------------------------
# separable: batch on "data", c_out on "model" (collective-free)
# ---------------------------------------------------------------------------

def _sep_sharded_impl(x, w_dw, w_pw, mesh, stride, padding, tile_h, dw_act,
                      act, interpret, residency, collective, in_layout):
    validate_layout(in_layout)
    sharded_in = in_layout == "model_sharded"
    _dp, mp = conv_mesh_shape(mesh)
    c_in, c_out = x.shape[-1], w_pw.shape[1]
    batch = _batch_axes(mesh)
    TRACE_COUNTS["separable"] += 1

    if not sharded_in:
        # classic partitioning: c_out on "model", c_in replicated — the PW
        # reduction is device-local, no collective
        _require_shardable(mesh, x.shape[0], c_out, "c_out")

        def local(xl, wdl, wpl):
            return _fused_impl(xl, wdl, wpl, stride, padding, tile_h,
                               dw_act, act, interpret, residency)

        return shard_map_compat(
            local, mesh,
            in_specs=(P(batch, None, None, None),   # batch slice, full C_in
                      P(None, None, None),          # DW taps replicated
                      P(None, MODEL_AXIS)),         # PW columns sharded
            out_specs=P(batch, None, None, MODEL_AXIS),
        )(x, w_dw, w_pw)

    # sharded-in partitioning: c_in on "model" — the DW is channel-local
    # on the arriving slice (no gather, the layout win), but the PW now
    # reduces over c_in ACROSS devices: each shard contracts its c_in
    # rows against the FULL c_out width, and the partials reduce per
    # ``collective``.  The output activation is nonlinear, so it must be
    # applied AFTER the reduction — the kernel runs with act=None and the
    # local body applies it to the reduced result.
    _require_shardable(mesh, x.shape[0], c_in, "c_in")
    cw = scatter_c_out(c_out, mp) if collective == "psum_scatter" else c_out

    def local_sharded(xl, wdl, wpl):
        out = _fused_impl(xl, wdl, wpl, stride, padding, tile_h, dw_act,
                          None, interpret, residency)
        if collective == "psum_scatter":
            if out.shape[-1] < cw:
                out = jnp.pad(out, ((0, 0), (0, 0), (0, 0),
                                    (0, cw - out.shape[-1])))
            out = jax.lax.psum_scatter(out, MODEL_AXIS,
                                       scatter_dimension=3, tiled=True)
        else:
            out = jax.lax.psum(out, MODEL_AXIS)
        return _act_ref(out, act).astype(out.dtype)

    out_spec = P(batch, None, None,
                 MODEL_AXIS if collective == "psum_scatter" else None)
    out = shard_map_compat(
        local_sharded, mesh,
        in_specs=(P(batch, None, None, MODEL_AXIS),  # batch + C_in slice
                  P(None, None, MODEL_AXIS),         # DW taps per channel
                  P(MODEL_AXIS, None)),              # PW rows sharded
        out_specs=out_spec,
    )(x, w_dw, w_pw)
    if cw != c_out:
        out = out[..., :c_out]
    return out


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12))
def _sep_sharded_op(x, w_dw, w_pw, mesh, stride, padding, tile_h, dw_act,
                    act, interpret, residency, collective, in_layout):
    return _sep_sharded_impl(x, w_dw, w_pw, mesh, stride, padding, tile_h,
                             dw_act, act, interpret, residency, collective,
                             in_layout)


def _sep_sharded_fwd(x, w_dw, w_pw, mesh, stride, padding, tile_h, dw_act,
                     act, interpret, residency, collective, in_layout):
    out = _sep_sharded_op(x, w_dw, w_pw, mesh, stride, padding, tile_h,
                          dw_act, act, interpret, residency, collective,
                          in_layout)
    # barrier: under the jitted entry, raw-input residuals get forwarded
    # and a cotangent double-counts (see compat.residual_barrier)
    return out, residual_barrier((x, w_dw, w_pw))


def _sep_sharded_bwd(mesh, stride, padding, tile_h, dw_act, act, interpret,
                     residency, collective, in_layout, res, g):
    x, w_dw, w_pw = res
    _, vjp = jax.vjp(
        lambda x_, wd_, wp_: separable_ref(
            x_, wd_, wp_, stride=stride, padding=padding, dw_act=dw_act,
            act=act),
        x, w_dw, w_pw,
    )
    return vjp(g)


_sep_sharded_op.defvjp(_sep_sharded_fwd, _sep_sharded_bwd)


@functools.lru_cache(maxsize=256)
def _sep_sharded_entry(mesh, stride, padding, tile_h, dw_act, act, interpret,
                       residency, collective, in_layout):
    """One jitted entry point per (mesh, static schedule).

    The lru_cache makes repeated calls at serving rate reuse ONE
    ``jax.jit`` callable, whose own cache then keys on shapes/dtypes — the
    shard_map closure is built once per trace instead of once per call."""

    @jax.jit
    def entry(x, w_dw, w_pw):
        return _sep_sharded_op(x, w_dw, w_pw, mesh, stride, padding, tile_h,
                               dw_act, act, interpret, residency, collective,
                               in_layout)

    return entry


def convdk_fused_separable_sharded(
    x: jax.Array,
    w_dw: jax.Array,
    w_pw: jax.Array,
    *,
    mesh,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    dw_act: Optional[str] = None,
    act: Optional[str] = None,
    interpret: Optional[bool] = None,
    residency: Optional[str] = None,
    collective: Optional[str] = None,
    in_layout: Optional[str] = None,
) -> jax.Array:
    """Mesh-sharded fused depthwise-separable block (differentiable).

    ``shard_map`` over ``mesh``: batch on "data" (jointly with "pod"
    when the mesh carries one) for both layouts, then per ``in_layout``:

    * ``"replicated"`` (default): output channels on "model"; every
      device runs the single-device fused kernel — including its
      strip-staging engine, per ``residency`` — on its (batch, c_out)
      tile.  The c_in reduction is device-local (c_in is replicated), so
      no collective is needed — per-device HBM traffic is the
      single-device model evaluated at the shard shape.  Requires
      ``c_out % model == 0``.
    * ``"model_sharded"``: INPUT channels on "model" — the block consumes
      a c_in-sharded arrival without a gather (the DW is channel-local on
      the slice), and the PW partials reduce per ``collective``
      ("ring_allreduce" psum, replicated output; "psum_scatter" leaves
      the output c_out-sharded, zero-padding non-dividing widths).  The
      output activation is applied after the reduction (it is nonlinear).
      Requires ``c_in % model == 0``.

    ``can_shard_fused`` pre-checks divisibility; the model layer falls
    back to the unsharded kernel when the grid does not divide.
    Dispatches through a cached jitted entry point, so repeated
    serving-rate calls do not re-trace the ``shard_map`` closure.
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    if collective is None:
        collective = DEFAULT_COLLECTIVE
    if in_layout is None:
        in_layout = DEFAULT_LAYOUT
    # resolve the residual-forwarding probe EAGERLY (it cannot run inside
    # the fwd trace; cheap once cached) so the barrier decision the trace
    # bakes in is the probed one, not the safe fallback
    residual_barrier_needed()
    telemetry.counter("sharded.dispatch.separable")
    telemetry.counter(f"sharded.collective.{collective}")
    return _sep_sharded_entry(mesh, stride, padding, tile_h, dw_act, act,
                              interpret, residency, collective, in_layout)(
        x, w_dw, w_pw)


# ---------------------------------------------------------------------------
# MBConv: batch on "data", c_mid on "model" (SE squeeze + projection psum)
# ---------------------------------------------------------------------------

def _mbconv_sharded_impl(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                         mesh, stride, padding, tile_h, mode, exp_act,
                         dw_act, interpret, residency, collective,
                         in_layout, se_act="silu", gate_act="sigmoid"):
    _require_shardable(mesh, x.shape[0], w_dw.shape[-1], "c_mid")
    validate_layout(in_layout)
    _dp, mp = conv_mesh_shape(mesh)
    c_in, c_out = x.shape[-1], w_proj.shape[1]
    # non-dividing c_out no longer rejects scatter: the projection pads to
    # the next model-factor multiple inside _mbconv_impl (zero columns
    # contribute zero partials — exact), and the gathered-global view is
    # sliced back to c_out below
    cw = scatter_c_out(c_out, mp) if collective == "psum_scatter" else c_out
    sharded_in = in_layout == "model_sharded"
    if sharded_in and c_in % mp != 0:
        raise ValueError(
            f"model_sharded in_layout needs c_in % {MODEL_AXIS} == 0, got "
            f"c_in={c_in} over {MODEL_AXIS}={mp}")
    # identity-expand blocks (expansion ratio 1: w_exp None, no expand
    # convolution) take their input channels sharded like c_mid — the
    # input's c_in slice IS the shard's c_mid slice, so a c_in-sharded
    # arrival enters FREE and a replicated one is sliced locally.  A real
    # expand is dense over ALL of c_in, so a sharded arrival must be
    # gathered back at the entry (priced as perfmodel's transition_words;
    # a row-sharded expand would need a psum BEFORE the nonlinear exp_act
    # inside pass 1 — not expressible at this level — and prices e>=1x
    # worse than the gather anyway).
    identity_expand = w_exp is None
    TRACE_COUNTS["mbconv"] += 1

    def local(xl, wel, wdl, s1l, b1l, s2l, b2l, wpl):
        if sharded_in and not identity_expand:
            # gather entry: the dense expand needs all of c_in
            xl = jax.lax.all_gather(xl, MODEL_AXIS, axis=3, tiled=True)
        return _mbconv_impl(xl, wel, wdl, s1l, b1l, s2l, b2l, wpl, stride,
                            padding, tile_h, mode, exp_act, dw_act,
                            interpret, residency, se_act=se_act,
                            gate_act=gate_act, axis_name=MODEL_AXIS,
                            collective=collective, scatter_width=cw)

    batch = _batch_axes(mesh)
    x_spec = P(batch, None, None,
               MODEL_AXIS if (sharded_in or identity_expand) else None)
    # the layout-aware exit: under psum_scatter each shard keeps only its
    # c_out slice, so the output leaves sharded on "model" — a following
    # PW/block that consumes c_out-sharded activations needs no regather
    # (the global VALUES are identical to the ring variant's)
    out_spec = P(batch, None, None,
                 MODEL_AXIS if collective == "psum_scatter" else None)
    out = shard_map_compat(
        local, mesh,
        in_specs=(x_spec,                           # batch slice (+ C_in
                                                    #   slice when sharded-in)
                  P(None, MODEL_AXIS),              # expand columns
                  P(None, None, MODEL_AXIS),        # DW taps per channel
                  P(MODEL_AXIS, None),              # squeeze FC rows
                  P(None),                          # squeeze bias (replicated:
                                                    #   added after the psum)
                  P(None, MODEL_AXIS),              # excite FC columns
                  P(MODEL_AXIS),                    # excite bias
                  P(MODEL_AXIS, None)),             # projection rows
        out_specs=out_spec,
    )(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj)
    if cw != c_out:
        out = out[..., :c_out]
    return out


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18,
                                    19, 20))
def _mbconv_sharded_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                       mesh, stride, padding, tile_h, mode, exp_act, dw_act,
                       interpret, residency, collective, in_layout,
                       se_act="silu", gate_act="sigmoid"):
    return _mbconv_sharded_impl(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2,
                                w_proj, mesh, stride, padding, tile_h, mode,
                                exp_act, dw_act, interpret, residency,
                                collective, in_layout, se_act, gate_act)


def _mbconv_sharded_fwd(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                        mesh, stride, padding, tile_h, mode, exp_act, dw_act,
                        interpret, residency, collective, in_layout,
                        se_act="silu", gate_act="sigmoid"):
    out = _mbconv_sharded_op(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2,
                             w_proj, mesh, stride, padding, tile_h, mode,
                             exp_act, dw_act, interpret, residency,
                             collective, in_layout, se_act, gate_act)
    # barrier: under the jitted entry, raw-input residuals get forwarded
    # and the w_dw cotangent double-counts (see compat.residual_barrier —
    # probe-gated, so it auto-disables on fixed JAX builds)
    return out, residual_barrier(
        (x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj))


def _mbconv_sharded_bwd(mesh, stride, padding, tile_h, mode, exp_act,
                        dw_act, interpret, residency, collective, in_layout,
                        se_act, gate_act, res, g):
    _, vjp = jax.vjp(
        lambda *p: mbconv_ref(*p, stride=stride, padding=padding,
                              exp_act=exp_act, dw_act=dw_act,
                              se_act=se_act, gate_act=gate_act),
        *res,
    )
    return vjp(g)


_mbconv_sharded_op.defvjp(_mbconv_sharded_fwd, _mbconv_sharded_bwd)


@functools.lru_cache(maxsize=256)
def _mbconv_sharded_entry(mesh, stride, padding, tile_h, mode, exp_act,
                          dw_act, interpret, residency, collective,
                          in_layout, se_act, gate_act, se):
    """One jitted entry point per (mesh, static schedule) — see
    ``_sep_sharded_entry``.  The collective AND entry layouts are part of
    the static schedule: ring/scatter and replicated/sharded-in variants
    are distinct entries, as are se=on/off (different arg pytrees)."""

    if se:
        @jax.jit
        def entry(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj):
            return _mbconv_sharded_op(x, w_exp, w_dw, w_se1, b_se1, w_se2,
                                      b_se2, w_proj, mesh, stride, padding,
                                      tile_h, mode, exp_act, dw_act,
                                      interpret, residency, collective,
                                      in_layout, se_act, gate_act)
    else:
        @jax.jit
        def entry(x, w_exp, w_dw, w_proj):
            return _mbconv_sharded_op(x, w_exp, w_dw, None, None, None,
                                      None, w_proj, mesh, stride, padding,
                                      tile_h, mode, exp_act, dw_act,
                                      interpret, residency, collective,
                                      in_layout, se_act, gate_act)

    return entry


def convdk_mbconv_fused_sharded(
    x: jax.Array,
    w_exp: Optional[jax.Array],
    w_dw: jax.Array,
    w_se1: Optional[jax.Array],
    b_se1: Optional[jax.Array],
    w_se2: Optional[jax.Array],
    b_se2: Optional[jax.Array],
    w_proj: jax.Array,
    *,
    mesh,
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
    collective: Optional[str] = None,
    in_layout: Optional[str] = None,
) -> jax.Array:
    """Mesh-sharded two-pass fused MBConv block (differentiable).

    ``shard_map`` over ``mesh``: batch on "data" (jointly with "pod" when
    the mesh carries one), the expanded c_mid grid on "model".  Each
    device runs both fused passes on its channel slice — staged per
    ``residency`` by the shared engine, including the double-buffered
    retained-DW re-read; the pass-1 SE pool crosses devices exactly once
    (a (B, C_se) squeeze ``psum`` before the pass-2 gate), and the pass-2
    projection partials reduce per ``collective``:

    * ``"ring_allreduce"`` (default): ``psum`` into the replicated block
      output;
    * ``"psum_scatter"``: ``psum_scatter`` over the channel dim — half
      the wire words, and the returned global array is SHARDED on c_out
      across "model" (identical values; a following PW/block that
      consumes c_out-sharded activations needs no regather).  A
      non-dividing c_out zero-pads the projection to the next
      model-factor multiple and slices it back (exact).

    ``in_layout`` declares the ARRIVAL layout the entry consumes:
    ``"replicated"`` (default) streams the full c_in per device;
    ``"model_sharded"`` (requires ``c_in % model == 0``) takes a
    c_in-sharded ``x`` — collective-free for identity-expand blocks
    (``w_exp=None`` with ``exp_act=None``: expansion ratio 1, no expand
    convolution, ``c_in == c_mid``), via an entry ``all_gather``
    otherwise (a real expand is dense over all of c_in).

    Collective + transition bytes are priced by
    ``core.perfmodel.sharded_mbconv_traffic`` under the same axes.

    Pass ALL FOUR SE params as ``None`` for a no-SE block (MobileNet-V3's
    early/middle stages): the pass-1 pool, the host MLP, the pass-2 gate
    AND the squeeze ``psum`` all disappear — an se=off block emits zero
    squeeze collectives on the mesh.  ``se_act``/``gate_act`` select the
    SE MLP nonlinearities ((relu, hard_sigmoid) for MobileNet-V3).

    Requires ``b % (pod*data) == 0`` and ``c_mid % model == 0``.
    Dispatches through a cached jitted entry point (no per-call
    re-tracing).
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    if collective is None:
        collective = DEFAULT_COLLECTIVE
    if in_layout is None:
        in_layout = DEFAULT_LAYOUT
    se = w_se1 is not None
    # resolve the residual-forwarding probe EAGERLY (see the separable
    # wrapper): the probe itself dispatches through _mbconv_sharded_op
    # with the probing flag set, so this never recurses
    residual_barrier_needed()
    telemetry.counter("sharded.dispatch.mbconv")
    telemetry.counter(f"sharded.collective.{collective}")
    entry = _mbconv_sharded_entry(mesh, stride, padding, tile_h, mode,
                                  exp_act, dw_act, interpret, residency,
                                  collective, in_layout, se_act, gate_act,
                                  se)
    # the shards take the block's narrow channels: a lane-layout arrival
    # is sliced at the shard boundary and the output padded back
    lanes = in_lane_layout(x)
    x = x[..., :w_dw.shape[-1] if w_exp is None else w_exp.shape[0]]
    if se:
        out = entry(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj)
    else:
        out = entry(x, w_exp, w_dw, w_proj)
    return as_layout(out, w_proj.shape[1], lanes)


# ---------------------------------------------------------------------------
# Fused-MBConv: batch on "data", c_mid on "model" (projection psum only)
# ---------------------------------------------------------------------------

def _fusedmb_sharded_impl(x, w_conv, w_proj, mesh, stride, padding, tile_h,
                          act, interpret, residency, collective, in_layout):
    _require_shardable(mesh, x.shape[0], w_conv.shape[-1], "c_mid")
    validate_layout(in_layout)
    if in_layout != "replicated":
        # a dense conv consumes EVERY input channel of every pixel — there
        # is no channel-local entry for a c_in-sharded arrival (unlike the
        # identity-expand MBConv), so the solver never offers one
        raise ValueError(
            f"fusedmb consumes replicated arrivals only, got {in_layout!r}")
    _dp, mp = conv_mesh_shape(mesh)
    # no projection (expansion ratio 1): each shard's conv channels are
    # its output channels, which leave sharded on "model" with no
    # collective
    project = w_proj is not None
    c_out = w_proj.shape[1] if project else w_conv.shape[-1]
    cw = scatter_c_out(c_out, mp) if collective == "psum_scatter" else c_out
    TRACE_COUNTS["fusedmb"] += 1

    def local(xl, wcl, wpl):
        return _fusedmb_impl(xl, wcl, wpl, stride, padding, tile_h, act,
                             interpret, residency, axis_name=MODEL_AXIS,
                             collective=collective, scatter_width=cw)

    batch = _batch_axes(mesh)
    out_spec = P(batch, None, None,
                 MODEL_AXIS if collective == "psum_scatter" or not project
                 else None)
    out = shard_map_compat(
        local, mesh,
        in_specs=(P(batch, None, None, None),       # batch slice, full C_in
                  P(None, None, None, MODEL_AXIS),  # conv c_mid planes
                  P(MODEL_AXIS, None)),             # projection rows
        out_specs=out_spec,
    )(x, w_conv, w_proj)
    if project and cw != c_out:
        out = out[..., :c_out]
    return out


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _fusedmb_sharded_op(x, w_conv, w_proj, mesh, stride, padding, tile_h,
                        act, interpret, residency, collective, in_layout):
    return _fusedmb_sharded_impl(x, w_conv, w_proj, mesh, stride, padding,
                                 tile_h, act, interpret, residency,
                                 collective, in_layout)


def _fusedmb_sharded_fwd(x, w_conv, w_proj, mesh, stride, padding, tile_h,
                         act, interpret, residency, collective, in_layout):
    out = _fusedmb_sharded_op(x, w_conv, w_proj, mesh, stride, padding,
                              tile_h, act, interpret, residency, collective,
                              in_layout)
    # barrier: under the jitted entry, raw-input residuals get forwarded
    # and a cotangent double-counts (see compat.residual_barrier)
    return out, residual_barrier((x, w_conv, w_proj))


def _fusedmb_sharded_bwd(mesh, stride, padding, tile_h, act, interpret,
                         residency, collective, in_layout, res, g):
    x, w_conv, w_proj = res
    _, vjp = jax.vjp(
        lambda x_, wc_, wp_: fusedmb_ref(
            x_, wc_, wp_, stride=stride, padding=padding, act=act),
        x, w_conv, w_proj,
    )
    return vjp(g)


_fusedmb_sharded_op.defvjp(_fusedmb_sharded_fwd, _fusedmb_sharded_bwd)


@functools.lru_cache(maxsize=256)
def _fusedmb_sharded_entry(mesh, stride, padding, tile_h, act, interpret,
                           residency, collective, in_layout):
    """One jitted entry point per (mesh, static schedule) — see
    ``_sep_sharded_entry``."""

    @jax.jit
    def entry(x, w_conv, w_proj):
        return _fusedmb_sharded_op(x, w_conv, w_proj, mesh, stride, padding,
                                   tile_h, act, interpret, residency,
                                   collective, in_layout)

    return entry


def convdk_fusedmb_fused_sharded(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: Optional[jax.Array],
    *,
    mesh,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    act: Optional[str] = "silu",
    interpret: Optional[bool] = None,
    residency: Optional[str] = None,
    collective: Optional[str] = None,
    in_layout: Optional[str] = None,
) -> jax.Array:
    """Mesh-sharded single-pass Fused-MBConv block (differentiable).

    ``shard_map`` over ``mesh``: batch on "data" (jointly with "pod" when
    the mesh carries one), the expanded c_mid grid on "model".  Each
    device runs the single-pass kernel on its channel slice of the dense
    conv — staged per ``residency`` by the shared engine — and the
    projection's c_mid reduction crosses devices per ``collective``
    (``psum`` replicated output, ``psum_scatter`` c_out-sharded exit at
    half the wire words; non-dividing c_out zero-pads and slices back,
    exact).  ``w_proj=None`` (expansion ratio 1, no projection) has no
    reduction: each device's output is its c_mid slice, and the block
    leaves sharded on "model" with no collective.  There is NO SE stage,
    so the block's only collective is the projection reduction — and no
    pass 2 at all: a pipelined consumer cannot hide behind this block
    (``core.autotune`` prices that honestly).

    ``in_layout`` must be ``"replicated"``: a dense conv consumes every
    input channel, so there is no channel-local entry for a sharded
    arrival (the network solver never offers fusedmb one).

    Requires ``b % (pod*data) == 0`` and ``c_mid % model == 0``.
    Dispatches through a cached jitted entry point (no per-call
    re-tracing).
    """
    if interpret is None:
        interpret = default_interpret()
    if residency is None:
        residency = DEFAULT_RESIDENCY
    if collective is None:
        collective = DEFAULT_COLLECTIVE
    if in_layout is None:
        in_layout = DEFAULT_LAYOUT
    residual_barrier_needed()
    telemetry.counter("sharded.dispatch.fusedmb")
    if w_proj is None:
        # no projection, no reduction: the collective is never issued
        telemetry.counter("kernels.fusedmb.no_project")
    else:
        telemetry.counter(f"sharded.collective.{collective}")
    k_h, k_w, c_in, c_mid = w_conv.shape
    if taps_per_pass(c_in, k_h * k_w) > 1:
        telemetry.counter("kernels.fusedmb.tap_packed")
    # the shards take the block's narrow channels: a lane-layout arrival
    # is sliced at the shard boundary and the output padded back
    lanes = in_lane_layout(x)
    out = _fusedmb_sharded_entry(mesh, stride, padding, tile_h, act,
                                 interpret, residency, collective,
                                 in_layout)(x[..., :c_in], w_conv, w_proj)
    return as_layout(out, c_mid if w_proj is None else w_proj.shape[1],
                     lanes)
