"""Pure-jnp oracles for the ConvDK Pallas kernels.

These are the ground truth the kernels are swept against (shapes x dtypes x
strides) in interpret mode.  They use only jnp / lax primitives.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def depthwise2d_ref(
    x: jax.Array, w: jax.Array, stride: int = 1, padding: str = "SAME"
) -> jax.Array:
    """Depthwise Conv2D oracle.  x: (B, H, W, C) NHWC; w: (k_h, k_w, C)."""
    k_h, k_w, c = w.shape
    rhs = jnp.transpose(w, (2, 0, 1))[:, None]  # (C, 1, k_h, k_w) OIHW
    out = jax.lax.conv_general_dilated(
        x, rhs,
        window_strides=(stride, stride),
        padding=padding,
        feature_group_count=c,
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
    )
    return out


def _act_ref(x: jax.Array, act: Optional[str]) -> jax.Array:
    if act is None:
        return x
    if act == "relu":
        return jnp.maximum(x, 0.0)
    if act == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    if act == "silu":
        return x * jax.nn.sigmoid(x)
    if act == "sigmoid":
        return jax.nn.sigmoid(x)
    if act == "hard_swish":
        return x * jnp.clip(x + 3.0, 0.0, 6.0) * (1.0 / 6.0)
    if act == "hard_sigmoid":
        return jnp.clip(x + 3.0, 0.0, 6.0) * (1.0 / 6.0)
    raise ValueError(f"unsupported activation: {act}")


def separable_ref(
    x: jax.Array,
    w_dw: jax.Array,
    w_pw: jax.Array,
    stride: int = 1,
    padding: str = "SAME",
    dw_act: Optional[str] = None,
    act: Optional[str] = None,
) -> jax.Array:
    """Depthwise-separable block oracle: DW conv -> dw_act -> 1x1 PW -> act.

    x: (B, H, W, C_in); w_dw: (k_h, k_w, C_in); w_pw: (C_in, C_out).
    The PW contraction runs in f32 (matching the fused kernel's accumulator)
    before casting back to the input dtype.
    """
    y = depthwise2d_ref(x, w_dw, stride=stride, padding=padding)
    y = _act_ref(y.astype(jnp.float32), dw_act)
    z = jax.lax.dot_general(
        y, w_pw.astype(jnp.float32),
        dimension_numbers=(((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return _act_ref(z, act).astype(x.dtype)


def mbconv_ref(
    x: jax.Array,
    w_exp: Optional[jax.Array],
    w_dw: jax.Array,
    w_se1: Optional[jax.Array],
    b_se1: Optional[jax.Array],
    w_se2: Optional[jax.Array],
    b_se2: Optional[jax.Array],
    w_proj: jax.Array,
    stride: int = 1,
    padding: str = "SAME",
    exp_act: Optional[str] = "silu",
    dw_act: Optional[str] = "silu",
    se_act: Optional[str] = "silu",
    gate_act: Optional[str] = "sigmoid",
) -> jax.Array:
    """MBConv (EfficientNet / MobileNet-V3) block oracle, WITHOUT the
    residual add:

        expand 1x1 -> exp_act -> depthwise k x k / s -> dw_act
        -> SE (global mean pool -> FC -> se_act -> FC -> gate_act, scales
           the DW output; skipped entirely when ``w_se1 is None``)
        -> project 1x1 (linear).

    x: (B, H, W, C_in); w_exp: (C_in, C_mid); w_dw: (k, k, C_mid);
    w_se1/b_se1: (C_mid, C_se)/(C_se,); w_se2/b_se2: (C_se, C_mid)/(C_mid,);
    w_proj: (C_mid, C_out).  For expand_ratio == 1 blocks pass
    ``w_exp=None`` with ``exp_act=None``: there is no expand, the depthwise
    taps act on ``x`` itself (the kernel does the same).  For
    no-SE blocks (MobileNet-V3's early/middle stages) pass ``w_se1=None``
    — the pool, both FCs and the gate multiply disappear, exactly like the
    se=off kernel path.  EfficientNet keeps the (silu, sigmoid) defaults;
    MobileNet-V3's SE uses ``se_act="relu"``/``gate_act="hard_sigmoid"``.
    All contractions run in f32, matching the fused kernel's accumulators.
    """
    e = x.astype(jnp.float32)
    if w_exp is None:
        assert exp_act is None, exp_act
    else:
        e = jax.lax.dot_general(
            e, w_exp.astype(jnp.float32),
            dimension_numbers=(((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    e = _act_ref(e, exp_act)
    d = depthwise2d_ref(e, w_dw.astype(jnp.float32), stride=stride,
                        padding=padding)
    d = _act_ref(d.astype(jnp.float32), dw_act)
    if w_se1 is not None:
        pooled = jnp.mean(d, axis=(1, 2))                   # (B, C_mid)
        s1 = _act_ref(pooled @ w_se1.astype(jnp.float32)
                      + b_se1.astype(jnp.float32), se_act)
        gate = _act_ref(s1 @ w_se2.astype(jnp.float32)
                        + b_se2.astype(jnp.float32), gate_act)
        d = d * gate[:, None, None, :]
    out = jax.lax.dot_general(
        d, w_proj.astype(jnp.float32),
        dimension_numbers=(((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


def fusedmb_ref(
    x: jax.Array,
    w_conv: jax.Array,
    w_proj: Optional[jax.Array],
    stride: int = 1,
    padding: str = "SAME",
    act: Optional[str] = "silu",
) -> jax.Array:
    """Fused-MBConv (EfficientNet-V2) block oracle, WITHOUT the residual:

        dense k x k / s conv (C_in -> C_mid) -> act -> project 1x1 (linear).

    The expand-PW and the depthwise conv of a classic MBConv collapse into
    ONE dense convolution; there is no SE stage (V2's fused stages run
    without it).  x: (B, H, W, C_in); w_conv: (k, k, C_in, C_mid) HWIO;
    w_proj: (C_mid, C_out), or None for the expansion-1 block, which has
    no projection: act(conv) is the output.  All contractions run in f32,
    matching the single-pass fused kernel's accumulators.
    """
    e = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w_conv.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    out = _act_ref(e, act)
    if w_proj is not None:
        out = jax.lax.dot_general(
            out, w_proj.astype(jnp.float32),
            dimension_numbers=(((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return out.astype(x.dtype)


def causal_conv1d_ref(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
) -> jax.Array:
    """Causal depthwise Conv1D oracle (the Mamba-2 / RecurrentGemma stem).

    x: (B, L, D); w: (k, D); out[t] = sum_i w[i] * x[t - k + 1 + i].
    """
    k, d = w.shape
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    if bias is not None:
        out = out + bias
    if activation == "silu":
        out = out * jax.nn.sigmoid(out)
    return out


def causal_conv1d_update_ref(
    state: jax.Array,
    x_t: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
):
    """Single-token decode step.  state: (B, k-1, D) last inputs; x_t: (B, D).

    Returns (y_t, new_state).
    """
    k, d = w.shape
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B, k, D)
    y = jnp.einsum("bkd,kd->bd", window, w)
    if bias is not None:
        y = y + bias
    if activation == "silu":
        y = y * jax.nn.sigmoid(y)
    return y, window[:, 1:, :]
