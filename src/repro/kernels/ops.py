"""Public jit'd wrappers for the ConvDK Pallas kernels.

``stage_row_strips`` / ``stage_seq_strips`` are the HBM->VMEM staging step —
the TPU analogue of the paper's IB->TRF strip loads: the input is laid out
as overlapping strips once, so each kernel grid cell consumes a plain
non-overlapping block (halo cost: (k - s) rows per tile_h*s rows, < 13 %;
the strips are the only extra HBM traffic, exactly as the TRF loads are the
only buffer traffic in the CIM macro).

On CPU (tests, smoke runs) the wrappers run the kernels in interpret mode;
pass ``interpret=False`` (default on TPU) for compiled Mosaic kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.perfmodel import pick_channel_block
from .common import default_interpret, round_up as _round_up, spatial_pads
from .convdk_conv1d import conv1d_pallas
from .convdk_dw import dw2d_pallas
from .ref import causal_conv1d_ref, depthwise2d_ref


def stage_row_strips(x: jax.Array, k: int, stride: int, tile_h: int) -> jax.Array:
    """(B, H_pad, W_pad, C) -> (B, n_th, (tile_h-1)*s + k, W_pad, C) strips."""
    b, h_pad, w_pad, c = x.shape
    in_rows = (tile_h - 1) * stride + k
    out_h = (h_pad - k) // stride + 1
    n_th = -(-out_h // tile_h)
    # pad the bottom so the final strip is full-size
    need = (n_th - 1) * tile_h * stride + in_rows
    if need > h_pad:
        x = jnp.pad(x, ((0, 0), (0, need - h_pad), (0, 0), (0, 0)))
    starts = jnp.arange(n_th) * (tile_h * stride)
    idx = starts[:, None] + jnp.arange(in_rows)[None, :]     # (n_th, in_rows)
    return x[:, idx]                                          # gather rows


def stage_seq_strips(x: jax.Array, k: int, tile_l: int) -> jax.Array:
    """(B, L, D) -> causal strips (B, n_tl, tile_l + k - 1, D)."""
    b, l, d = x.shape
    n_tl = -(-l // tile_l)
    xp = jnp.pad(x, ((0, 0), (k - 1, n_tl * tile_l - l), (0, 0)))
    starts = jnp.arange(n_tl) * tile_l
    idx = starts[:, None] + jnp.arange(tile_l + k - 1)[None, :]
    return xp[:, idx]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _dw2d_op(x, w, stride, padding, tile_h, interpret):
    return _dw2d_impl(x, w, stride, padding, tile_h, interpret)


def _dw2d_fwd(x, w, stride, padding, tile_h, interpret):
    return _dw2d_op(x, w, stride, padding, tile_h, interpret), (x, w)


def _dw2d_bwd(stride, padding, tile_h, interpret, res, g):
    # Backward through the mathematically identical jnp reference — the
    # kernel computes the same convolution, so the VJP is exact.
    x, w = res
    _, vjp = jax.vjp(
        lambda x_, w_: depthwise2d_ref(x_, w_, stride=stride, padding=padding),
        x, w,
    )
    return vjp(g)


_dw2d_op.defvjp(_dw2d_fwd, _dw2d_bwd)


@functools.partial(
    jax.jit, static_argnames=("stride", "padding", "tile_h", "interpret")
)
def convdk_depthwise2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    padding: str = "SAME",
    tile_h: int = 8,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Depthwise Conv2D via the ConvDK Pallas kernel (differentiable).

    x: (B, H, W, C) NHWC; w: (k_h, k_w, C).  Returns (B, H', W', C).
    """
    if interpret is None:
        interpret = default_interpret()
    return _dw2d_op(x, w, stride, padding, tile_h, interpret)


def _dw2d_impl(x, w, stride, padding, tile_h, interpret):
    b, h, w_in, c = x.shape
    k_h, k_w, cw = w.shape
    assert cw == c, (cw, c)
    s = stride
    out_h, out_w, pads = spatial_pads(h, w_in, k_h, k_w, s, padding)

    # channel blocking: 128-lane blocks of the lane-padded width
    c_block = pick_channel_block(c)
    c_pad = _round_up(c, c_block)
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, c_pad - c)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, c_pad - c)))
    # ensure the width slice i + s*(out_w-1) + 1 stays in bounds
    need_w = (out_w - 1) * s + k_w
    if need_w > xp.shape[2]:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, need_w - xp.shape[2]), (0, 0)))

    tile_h = min(tile_h, out_h)
    strips = stage_row_strips(xp, k_h, s, tile_h)        # IB->TRF staging
    out = dw2d_pallas(
        strips, wp, stride=s, out_w=out_w, tile_h=tile_h,
        c_block=c_block, interpret=interpret,
    )                                                     # (B, n_th, TH, W', C)
    out = out.reshape(b, -1, out_w, c_pad)[:, :out_h, :, :c]
    return out


@functools.partial(
    jax.jit,
    static_argnames=("stride", "padding", "tile_h", "dw_act", "act",
                     "interpret"),
)
def convdk_separable_staged(
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
) -> jax.Array:
    """The STAGED two-kernel separable pipeline (comparison baseline).

    Runs the DW ConvDK kernel over pre-staged strips, round-trips the DW
    output through HBM, then applies the pointwise projection as a separate
    matmul — the exact double HBM trip ``convdk_fused_separable`` fuses away.
    Kept as the reference executable for the fused-vs-staged traffic and
    numerics comparisons (benchmarks/kernel_bench.py, tests).
    """
    from .ref import _act_ref  # local import: ref has no dep on ops
    y = convdk_depthwise2d(x, w_dw, stride=stride, padding=padding,
                           tile_h=tile_h, interpret=interpret)
    y = _act_ref(y.astype(jnp.float32), dw_act)
    z = jnp.einsum("bhwc,cd->bhwd", y, w_pw.astype(jnp.float32))
    return _act_ref(z, act).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv1d_op(x, w, bias, activation, tile_l, interpret):
    return _conv1d_impl(x, w, bias, activation, tile_l, interpret)


def _conv1d_fwd(x, w, bias, activation, tile_l, interpret):
    return _conv1d_op(x, w, bias, activation, tile_l, interpret), (x, w, bias)


def _conv1d_bwd(activation, tile_l, interpret, res, g):
    x, w, bias = res
    _, vjp = jax.vjp(
        lambda x_, w_, b_: causal_conv1d_ref(x_, w_, b_, activation=activation),
        x, w, bias,
    )
    return vjp(g)


_conv1d_op.defvjp(_conv1d_fwd, _conv1d_bwd)


@functools.partial(
    jax.jit, static_argnames=("activation", "tile_l", "interpret")
)
def convdk_causal_conv1d(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    activation: Optional[str] = None,
    tile_l: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal depthwise Conv1D (+ fused bias/SiLU) via the ConvDK kernel
    (differentiable).

    x: (B, L, D); w: (k, D); bias: (D,) or None.  Returns (B, L, D).
    """
    if interpret is None:
        interpret = default_interpret()
    if bias is None:
        bias = jnp.zeros((x.shape[-1],), x.dtype)
    return _conv1d_op(x, w, bias, activation, tile_l, interpret)


def _conv1d_impl(x, w, bias, activation, tile_l, interpret):
    b, l, d = x.shape
    k, dw = w.shape
    assert dw == d, (dw, d)

    d_block = min(128, _round_up(d, 8))
    d_pad = _round_up(d, d_block)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))
    wp = jnp.pad(w, ((0, 0), (0, d_pad - d)))
    bp = jnp.pad(bias, (0, d_pad - d))

    tile_l_eff = min(tile_l, _round_up(l, 8))
    strips = stage_seq_strips(xp, k, tile_l_eff)          # IB->TRF staging
    out = conv1d_pallas(
        strips, wp, bp, tile_l=tile_l_eff, activation=activation,
        d_block=d_block, interpret=interpret,
    )                                                     # (B, n_tl, TL, D)
    return out.reshape(b, -1, d_pad)[:, :l, :d]
