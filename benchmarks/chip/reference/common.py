"""Plain building blocks of the reference networks.

Straightforward ``jax.numpy``/``lax`` code: no kernels, no schedules, no
batching tricks, and nothing imported from the program under test.  Every
function computes in the dtype of its inputs: float32 traced under
``jax.default_matmul_precision("highest")`` is the reference.

``passes=3`` computes every contraction (convolution or matrix product)
as three bfloat16 passes, the algorithm of ``Precision.HIGH``: each
float32 operand is split into a bfloat16 head and a bfloat16 tail, and
head x head + head x tail + tail x head are accumulated in float32 (the
tail x tail product is dropped).  Written out, it gives the same control
on any backend.  The head is cut with integer operations on the bits:
a float32 -> bfloat16 -> float32 round trip may be folded away by XLA
(excess precision), which would leave the tail zero.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def act(x, name: Optional[str]):
    """The activations the configurations name (arXiv:1905.02244 eq. 1-2
    for the hard variants)."""
    if name is None:
        return x
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "sigmoid":
        return jax.nn.sigmoid(x)
    if name == "hard_sigmoid":
        return jnp.clip(x + 3, 0, 6) / 6
    if name == "hard_swish":
        return x * (jnp.clip(x + 3, 0, 6) / 6)
    raise ValueError(f"unknown activation {name!r}")


def round_filters(c: int, width_mult: float, divisor: int = 8) -> int:
    """EfficientNet's channel rounding (arXiv:1905.11946, reference code):
    scale, snap to the divisor, never below 90% of the scaled width."""
    if width_mult == 1.0:
        return c
    scaled = c * width_mult
    new_c = max(divisor, int(scaled + divisor / 2) // divisor * divisor)
    if new_c < 0.9 * scaled:
        new_c += divisor
    return int(new_c)


def make_divisible(v: float, divisor: int = 8) -> int:
    """MobileNet's channel rounding (arXiv:1905.02244, reference code
    ``_make_divisible``): the nearest multiple of the divisor, never below
    90% of ``v``."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return int(new_v)


@dataclasses.dataclass(frozen=True)
class Block:
    """One inverted-bottleneck block at logical widths."""

    c_in: int
    c_mid: int
    c_out: int
    k: int
    s: int
    c_se: int            # 0: no squeeze-and-excitation
    act: str             # expand and depthwise activation
    se_act: str
    gate_act: str

    @property
    def expands(self) -> bool:
        return self.c_mid != self.c_in

    @property
    def residual(self) -> bool:
        return self.s == 1 and self.c_in == self.c_out


# One weight leaf: (shape, init, scale).  Inits follow a fan-in scaled
# truncated normal, std = scale / sqrt(fan_in), fan_in = prod(shape[:-1]).
Leaf = Tuple[Tuple[int, ...], str, float]


def block_leaves(b: Block) -> Dict[str, Leaf]:
    """The weights of one block, under the names the served entry takes."""
    p: Dict[str, Leaf] = {
        "dw": ((b.k, b.k, b.c_mid), "normal", 1.0),
        "proj": ((b.c_mid, b.c_out), "normal", 2.0),
    }
    if b.c_se:
        p["se_w1"] = ((b.c_mid, b.c_se), "normal", 2.0)
        p["se_b1"] = ((b.c_se,), "zeros", 0.0)
        p["se_w2"] = ((b.c_se, b.c_mid), "normal", 2.0)
        p["se_b2"] = ((b.c_mid,), "zeros", 0.0)
    if b.expands:
        p["exp"] = ((b.c_in, b.c_mid), "normal", 2.0)
    return p


def init_leaf(leaf: Leaf, key, dtype=jnp.float32):
    shape, init, scale = leaf
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
    std = scale / np.sqrt(max(1, fan_in))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def contract(f, x, w, passes: Optional[int] = None):
    """``f(x, w)`` for a bilinear ``f``: as written, or in three bfloat16
    passes (see the module's docstring)."""
    if passes is None:
        return f(x, w, None)
    if passes != 3:
        raise ValueError(f"passes must be None or 3, got {passes}")
    xh, xl = _split(x)
    wh, wl = _split(w)
    f32 = jnp.float32
    return f(xh, wh, f32) + f(xh, wl, f32) + f(xl, wh, f32)


def _split(a):
    """float32 ``a`` -> (head, tail) in bfloat16 with head + tail ~= a:
    the head is ``a`` rounded to the nearest bfloat16 (ties to even)."""
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    head = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    return head.astype(jnp.bfloat16), (a - head).astype(jnp.bfloat16)


def _conv(stride: int, groups: int):
    def f(x, w, out):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, preferred_element_type=out)
    return f


def conv(x, w, stride: int, passes=None):
    """Dense k x k convolution, NHWC x HWIO, SAME padding (the extra pad
    row and column go to the bottom and right, as in TensorFlow)."""
    return contract(_conv(stride, 1), x, w, passes)


def depthwise(x, w, stride: int, passes=None):
    """Depthwise k x k convolution; ``w`` is (k, k, C)."""
    return contract(_conv(stride, w.shape[-1]), x, w[:, :, None, :], passes)


def pointwise(x, w, passes=None):
    return contract(lambda a, b, out: jnp.einsum(
        "bhwc,cd->bhwd", a, b, preferred_element_type=out), x, w, passes)


def dense(x, w, passes=None):
    return contract(lambda a, b, out: jnp.einsum(
        "bc,cd->bd", a, b, preferred_element_type=out), x, w, passes)


def block(x, p: dict, b: Block, passes=None):
    """expand 1x1 -> act -> depthwise k x k / s -> act -> SE -> project
    1x1 (linear), plus the identity residual where shapes allow."""
    y = act(pointwise(x, p["exp"], passes), b.act) if b.expands else x
    y = act(depthwise(y, p["dw"], b.s, passes), b.act)
    if b.c_se:
        pooled = y.mean(axis=(1, 2))
        g = act(dense(pooled, p["se_w1"], passes) + p["se_b1"], b.se_act)
        g = act(dense(g, p["se_w2"], passes) + p["se_b2"], b.gate_act)
        y = y * g[:, None, None, :]
    y = pointwise(y, p["proj"], passes)
    return y + x if b.residual else y


def chain(x, params: dict, blocks: List[Block], passes=None):
    for i, b in enumerate(blocks):
        x = block(x, params[f"block{i}"], b, passes)
    return x
