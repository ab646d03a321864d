"""Shared helpers for the ConvDK kernel wrappers.

One home for the padding arithmetic, the interpret-mode default and the
Mosaic compiler parameters, so the fused separable, MBConv and staged
pipelines can never desynchronize on them.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ..core.perfmodel import LANES, SUBLANES, launch_width


def default_interpret() -> bool:
    """Pallas interpret-mode default: interpret on CPU backends, compiled
    Mosaic otherwise.  Decided at each call (at trace time under jit), so
    importing the package initializes no backend."""
    return jax.default_backend() == "cpu"


def compiler_params() -> pltpu.CompilerParams:
    """Mosaic parameters of every ConvDK kernel launch.  The scoped-VMEM
    limit IS the autotuner's budget (``core.autotune.TPUConfig``), so a
    schedule the solver admits is one the compiler accepts."""
    from ..core.autotune import TPUConfig
    return pltpu.CompilerParams(vmem_limit_bytes=TPUConfig().vmem_bytes)


def store_out(o_ref, val):
    """Write a computed ``(tile_h, out_wk, C)`` output tile into a
    ``(1, tile_h, out_w, C)`` output block, which holds only the image's
    ``out_w`` columns (the cover columns are computed, never stored)."""
    o_ref[0] = val[:, :o_ref.shape[2]].astype(o_ref.dtype)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def spatial_pads(
    h: int, w_in: int, k_h: int, k_w: int, s: int, padding: str
) -> Tuple[int, int, Tuple[Tuple[int, int], Tuple[int, int]]]:
    """(out_h, out_w, ((top, bottom), (left, right))) for one conv layout.

    SAME matches ``jax.lax.conv_general_dilated``'s split (extra pad goes
    to the bottom/right); VALID pads nothing.
    """
    if padding == "SAME":
        out_h, out_w = -(-h // s), -(-w_in // s)
        ph = max(0, (out_h - 1) * s + k_h - h)
        pw = max(0, (out_w - 1) * s + k_w - w_in)
        pads = ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2))
    elif padding == "VALID":
        out_h, out_w = (h - k_h) // s + 1, (w_in - k_w) // s + 1
        pads = ((0, 0), (0, 0))
    else:
        raise ValueError(padding)
    return out_h, out_w, pads


class LaunchGeometry(NamedTuple):
    """How one strip-tiled conv launch covers its input.

    The kernels stage from the block's activation as it is, with no
    padded copy: each staged window is a ``(rows, w_tot, C)`` frame whose
    image pixels sit at row ``top`` and column ``left`` of the padded
    input, the rest of the frame zero (built in VMEM by
    ``kernels.staging``).  ``h_tot`` is the frame's height: SAME/VALID
    padding plus the height cover of the last strip.  ``w_tot`` is its
    width: the reach of ``out_wk`` taps, in whole sublanes.  ``out_wk``
    is the kernel's computed output width — ``out_w`` rounded up to whole
    sublanes (``perfmodel.launch_width``); only the first ``out_w``
    columns are stored."""

    out_h: int
    out_w: int
    out_wk: int
    tile_h: int
    n_th: int
    top: int
    left: int
    h_tot: int
    w_tot: int


def launch_geometry(h: int, w_in: int, k_h: int, k_w: int, s: int,
                    padding: str, tile_h: int) -> LaunchGeometry:
    out_h, out_w, ((top, bottom), (left, right)) = spatial_pads(
        h, w_in, k_h, k_w, s, padding)
    tile_h = max(1, min(tile_h, out_h))
    n_th = -(-out_h // tile_h)
    out_wk, w_tot = launch_width(out_w, s, k_w, w_in + left + right)
    h_tot = max(h + top + bottom, (n_th * tile_h - 1) * s + k_h)
    return LaunchGeometry(out_h, out_w, out_wk, tile_h, n_th, top, left,
                          h_tot, w_tot)


# -- the lane layout the block chain carries ---------------------------------
#
# Between two blocks an activation stays in the layout the next kernel
# stages from: channels rounded up to whole 128-lane groups, zero from the
# real channel count up.  HBM stores a narrow float32 channel dim in whole
# 128-lane tiles anyway, so the zero tail costs no bytes, and a strip DMA
# can address the array only at that width.  An array whose channel dim
# is a whole number of lane groups is taken to be in that layout; the
# kernels read the real channel count from their weights.

def in_lane_layout(x) -> bool:
    """Whether ``x``'s channel dim is a whole number of 128-lane groups."""
    return x.shape[-1] % LANES == 0


def to_lanes(x):
    """``x`` with its channels zero-padded up to whole 128-lane groups (a
    copy only when they are not already)."""
    c = x.shape[-1]
    if c % LANES == 0:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, round_up(c, LANES)
                                                    - c),))


def as_layout(y, c: int, lanes: bool):
    """A block output in its caller's layout: ``y`` (c real channels,
    possibly lane-padded) lane-padded when ``lanes``, else its first
    ``c`` channels."""
    return to_lanes(y) if lanes else y[..., :c]


def _needs_width_cover(x, c_block: int) -> bool:
    return x.shape[2] % SUBLANES != 0 and x.shape[-1] > c_block


def strip_source(x, c_block: int):
    """The array a kernel's strip copies read: ``x`` (lane layout) itself,
    unless its channels span several ``c_block`` blocks and its width is
    not a whole number of sublanes.  Mosaic slices a channel block out
    of an array only at such a width, so there ``x`` gets its width
    zero-padded up to whole sublanes — a copy, at 7 x 7 and 12 x 12 in
    the benchmarked networks; the zero columns fall in the right halo."""
    if not _needs_width_cover(x, c_block):
        return x
    w = x.shape[2]
    return jnp.pad(x, ((0, 0), (0, 0), (0, round_up(w, SUBLANES) - w),
                       (0, 0)))


def stages_in_place(x) -> bool:
    """Whether a block's kernels stage ``x`` as it is: in the lane layout
    and needing no width cover (``strip_source`` with the kernels'
    128-lane channel block), so the whole conv halo is built in VMEM and
    no XLA op copies ``x`` in front of the launch."""
    return in_lane_layout(x) and not _needs_width_cover(x, LANES)
