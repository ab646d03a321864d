"""Shared helpers for the ConvDK kernel wrappers.

One home for the padding arithmetic, the interpret-mode default and the
Mosaic compiler parameters, so the fused separable, MBConv and staged
pipelines can never desynchronize on them.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
from jax.experimental.pallas import tpu as pltpu

from ..core.perfmodel import launch_width


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

    ``out_wk`` is the kernel's output width — ``out_w`` rounded up to whole
    sublanes (``perfmodel.launch_width``); its extra columns are sliced
    off.  ``pads`` is the jnp.pad spec of the (B, H, W) dims: SAME/VALID
    padding plus the height cover of the last strip and the width cover of
    ``out_wk`` taps, in whole sublanes."""

    out_h: int
    out_w: int
    out_wk: int
    tile_h: int
    n_th: int
    pads: Tuple[Tuple[int, int], ...]


def launch_geometry(h: int, w_in: int, k_h: int, k_w: int, s: int,
                    padding: str, tile_h: int) -> LaunchGeometry:
    out_h, out_w, ((top, bottom), (left, right)) = spatial_pads(
        h, w_in, k_h, k_w, s, padding)
    tile_h = max(1, min(tile_h, out_h))
    n_th = -(-out_h // tile_h)
    out_wk, w_tot = launch_width(out_w, s, k_w, w_in + left + right)
    need_h = (n_th * tile_h - 1) * s + k_h
    bottom += max(0, need_h - (h + top + bottom))
    right = w_tot - w_in - left
    return LaunchGeometry(out_h, out_w, out_wk, tile_h, n_th,
                          ((0, 0), (top, bottom), (left, right)))
