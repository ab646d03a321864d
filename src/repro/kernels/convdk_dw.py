"""ConvDK depthwise-Conv2D Pallas TPU kernel.

TPU adaptation of the paper's ConvDK dataflow (DESIGN.md §Pillar B):

* CIM TRF strip  ->  a VMEM-resident input strip per grid cell.  The strip is
  staged ONCE from HBM (the IB->TRF load) and then re-read at the kernel-tap
  offsets — the l = lcm(k,s)/s shift cycles of Algorithm 1.  For s = 1 the
  tap loop over ``i`` IS the shift schedule (l = k, every block n active per
  cycle, Theorem-2 coverage = the polyphase identity m = n*k + a); for s = 2
  the strided slices realize the (a, n -> m) arithmetic progressions.
* CIM TM kernel duplication  ->  the weight tap w[j, i, :] is broadcast
  across all N output blocks of the strip in ONE vector op (the VPU plays
  the 180-row multi-access TM; duplication costs no extra HBM reads).
* BIG/LITTLE channel packing  ->  the channel-block grid dimension: channels
  ride the 128-wide lane axis, strips of ``tile_h`` output rows ride the
  grid, mirroring kernel duplication across idle tiles.

The kernel consumes pre-staged overlapping row strips (built by
``ops.stage_row_strips``, the IB->TRF analogue) so every BlockSpec is a plain
non-overlapping block: strip t holds input rows [t*TH*s, t*TH*s + (TH-1)*s + k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import compiler_params
from .staging import strided_read


def _dw2d_kernel(x_ref, w_ref, o_ref, *, k_h: int, k_w: int, stride: int,
                 tile_h: int, out_w: int):
    """One (batch, row-strip, channel-block) grid cell.

    x_ref: (1, 1, (tile_h-1)*s + k_h, W_pad, CB)  VMEM strip (the "TRF")
    w_ref: (k_h, k_w, CB)                         stationary taps (the "TM")
    o_ref: (1, 1, tile_h, out_w, CB)
    """
    s = stride
    acc = jnp.zeros((tile_h, out_w, x_ref.shape[-1]), jnp.float32)
    # l shift cycles x k_h row taps: every re-read of the resident strip is
    # one (a, j) pass of Algorithm 2; all N width-blocks update in parallel.
    # A stride-2 tap is a strided VMEM load: Mosaic cannot stride-slice a
    # loaded value.
    for j in range(k_h):
        for i in range(k_w):
            xs = strided_read(x_ref, (0, 0), j, i, tile_h, out_w, s)
            acc = acc + xs.astype(jnp.float32) * w_ref[j, i].astype(jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


def dw2d_pallas(
    x_strips: jax.Array,
    w: jax.Array,
    *,
    stride: int,
    out_w: int,
    tile_h: int,
    c_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Run the ConvDK DW2D kernel over pre-staged strips.

    x_strips : (B, n_th, in_rows, W_pad, C) with in_rows = (tile_h-1)*s + k_h
    w        : (k_h, k_w, C)
    returns  : (B, n_th, tile_h, out_w, C)
    """
    b, n_th, in_rows, w_pad, c = x_strips.shape
    k_h, k_w, _ = w.shape
    assert c % c_block == 0, (c, c_block)
    grid = (b, n_th, c // c_block)

    kernel = functools.partial(
        _dw2d_kernel, k_h=k_h, k_w=k_w, stride=stride,
        tile_h=tile_h, out_w=out_w,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, in_rows, w_pad, c_block),
                lambda bi, ti, ci: (bi, ti, 0, 0, ci),
            ),
            pl.BlockSpec((k_h, k_w, c_block), lambda bi, ti, ci: (0, 0, ci)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile_h, out_w, c_block),
            lambda bi, ti, ci: (bi, ti, 0, 0, ci),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_th, tile_h, out_w, c), x_strips.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="dw2d",
    )(x_strips, w)
