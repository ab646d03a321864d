"""ConvDK causal depthwise-Conv1D Pallas TPU kernel.

This is the performance-critical stem of Mamba-2 (d_conv = 4) and
RecurrentGemma (temporal conv, width 4) — the two assigned architectures the
paper's technique applies to (DESIGN.md §Arch-applicability).

ConvDK mapping (stride 1, so l = k and the shift schedule is the polyphase
identity; Condition 1's odd-k requirement is only needed for s > 1, see
DESIGN.md): the sequence strip rests in VMEM (TRF role) and is re-read at k
static shift offsets; each tap multiplies ALL blocks of the strip in one
vector op (TM kernel duplication role).  Channels ride the 128-lane axis.

Optional fusions: bias add and SiLU (both Mamba-2 and RG-LRU apply SiLU
right after the conv), saving one HBM round-trip of the (B, L, D) tensor.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv1d_kernel(x_ref, w_ref, b_ref, o_ref, *, k: int, tile_l: int,
                   activation: Optional[str]):
    """x_ref: (1, 1, tile_l + k - 1, DB); w_ref: (k, DB); b_ref: (1, DB)."""
    x = x_ref[0, 0]                                   # (tile_l + k - 1, DB)
    acc = jnp.zeros((tile_l, x.shape[-1]), jnp.float32)
    for i in range(k):                                # k shift cycles
        xs = jax.lax.slice(x, (i, 0), (i + tile_l, x.shape[-1]))
        acc = acc + xs.astype(jnp.float32) * w_ref[i].astype(jnp.float32)
    acc = acc + b_ref[0].astype(jnp.float32)
    if activation == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


def conv1d_pallas(
    x_strips: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    *,
    tile_l: int,
    activation: Optional[str] = None,
    d_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Run the ConvDK causal conv1d kernel over pre-staged strips.

    x_strips : (B, n_tl, tile_l + k - 1, D)  — strip t holds (left-padded)
               sequence positions [t*tile_l, t*tile_l + tile_l + k - 1)
    w        : (k, D);  bias: (D,)
    returns  : (B, n_tl, tile_l, D)
    """
    b, n_tl, in_len, d = x_strips.shape
    k, _ = w.shape
    assert in_len == tile_l + k - 1, (in_len, tile_l, k)
    assert d % d_block == 0, (d, d_block)
    grid = (b, n_tl, d // d_block)

    kernel = functools.partial(
        _conv1d_kernel, k=k, tile_l=tile_l, activation=activation
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, in_len, d_block), lambda bi, ti, di: (bi, ti, 0, di)
            ),
            pl.BlockSpec((k, d_block), lambda bi, ti, di: (0, di)),
            pl.BlockSpec((1, d_block), lambda bi, ti, di: (0, di)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile_l, d_block), lambda bi, ti, di: (bi, ti, 0, di)
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_tl, tile_l, d), x_strips.dtype),
        interpret=interpret,
        name="conv1d",
    )(x_strips, w, bias[None, :])
