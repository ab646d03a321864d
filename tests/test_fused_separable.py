"""Fused depthwise-separable ConvDK kernel vs the XLA oracle, the autotune
schedule layer, and the fused-vs-staged HBM traffic accounting."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotune import (
    TPUConfig,
    candidate_schedules,
    get_fused_schedule,
    select_fused_schedule,
    vmem_footprint_bytes,
)
from repro.core.perfmodel import (
    SeparableShape,
    fused_separable_traffic,
    staged_separable_traffic,
)
from repro.core.workloads import MOBILENET_V2_SEPARABLE
from repro.kernels import (
    convdk_fused_separable,
    convdk_separable_staged,
    separable_ref,
)

TOL = dict(rtol=1e-4, atol=1e-4)


def _rand(rng, shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _oracle(x, w_dw, w_pw, stride, padding="SAME"):
    """Independent oracle: lax depthwise conv composed with lax.dot_general
    for the pointwise stage (NOT the repo's separable_ref)."""
    k_h, k_w, c = w_dw.shape
    dw = jax.lax.conv_general_dilated(
        x, jnp.transpose(w_dw, (2, 0, 1))[:, None],
        window_strides=(stride, stride), padding=padding,
        feature_group_count=c,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))
    return jax.lax.dot_general(
        dw, w_pw, dimension_numbers=(((3,), (0,)), ((), ())))


# ---------------------------------------------------------------------------
# numerics vs the XLA DW+PW oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_fused_matches_xla_oracle(k, stride, padding):
    rng = np.random.default_rng(k * 10 + stride)
    b, h, w_in, ci, co = 2, 15, 19, 24, 40        # odd H, odd W
    x = _rand(rng, (b, h, w_in, ci))
    w_dw = _rand(rng, (k, k, ci))
    w_pw = _rand(rng, (ci, co))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=stride,
                                 padding=padding, interpret=True)
    want = _oracle(x, w_dw, w_pw, stride, padding)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("shape", [
    (1, 7, 7, 8, 16),        # LITTLE-regime ifmap, tiny channels
    (2, 13, 11, 130, 40),    # >128 input channels: multi-ci-block reduction
    (1, 9, 33, 32, 200),     # >128 output channels: multi-co-block grid
    (3, 28, 28, 96, 24),     # MobileNet-V2-like block
])
def test_fused_shape_sweep(shape):
    rng = np.random.default_rng(1)
    b, h, w_in, ci, co = shape
    x = _rand(rng, (b, h, w_in, ci))
    w_dw = _rand(rng, (3, 3, ci))
    w_pw = _rand(rng, (ci, co))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=1, interpret=True)
    want = _oracle(x, w_dw, w_pw, 1)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("tile_h", [1, 3, 8, 32])
def test_fused_tile_h_invariant(tile_h):
    """Any tile_h gives the same numbers — schedule is perf-only."""
    rng = np.random.default_rng(7)
    x = _rand(rng, (1, 17, 13, 16))
    w_dw = _rand(rng, (3, 3, 16))
    w_pw = _rand(rng, (16, 24))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=2, tile_h=tile_h,
                                 interpret=True)
    want = _oracle(x, w_dw, w_pw, 2)
    np.testing.assert_allclose(got, want, **TOL)


def test_fused_mid_block_activation():
    """dw_act fuses exactly: DW is depthwise, so the per-channel-block DW
    accumulator is final before the PW contraction."""
    rng = np.random.default_rng(3)
    x = _rand(rng, (2, 12, 12, 16))
    w_dw = _rand(rng, (3, 3, 16))
    w_pw = _rand(rng, (16, 8))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=1, dw_act="relu6",
                                 act="relu", interpret=True)
    dw = jax.lax.conv_general_dilated(
        x, jnp.transpose(w_dw, (2, 0, 1))[:, None], (1, 1), "SAME",
        feature_group_count=16, dimension_numbers=("NHWC", "OIHW", "NHWC"))
    want = jax.nn.relu(jnp.clip(dw, 0.0, 6.0) @ w_pw)
    np.testing.assert_allclose(got, want, **TOL)


def test_fused_matches_staged_pipeline():
    """The fused kernel and the staged two-kernel path are the same math."""
    rng = np.random.default_rng(9)
    x = _rand(rng, (2, 14, 14, 48))
    w_dw = _rand(rng, (5, 5, 48))
    w_pw = _rand(rng, (48, 64))
    for s in (1, 2):
        fused = convdk_fused_separable(x, w_dw, w_pw, stride=s,
                                       dw_act="relu", interpret=True)
        staged = convdk_separable_staged(x, w_dw, w_pw, stride=s,
                                         dw_act="relu", interpret=True)
        np.testing.assert_allclose(fused, staged, **TOL)


def test_fused_grad_matches_reference():
    rng = np.random.default_rng(5)
    x = _rand(rng, (1, 10, 11, 8))
    w_dw = _rand(rng, (3, 3, 8))
    w_pw = _rand(rng, (8, 12))

    def loss(fn):
        return lambda x_, wd_, wp_: (fn(x_, wd_, wp_) ** 2).sum()

    f = loss(lambda a, b, c: convdk_fused_separable(
        a, b, c, stride=2, dw_act="relu", interpret=True))
    r = loss(lambda a, b, c: separable_ref(a, b, c, stride=2, dw_act="relu"))
    g = jax.grad(f, argnums=(0, 1, 2))(x, w_dw, w_pw)
    g_ref = jax.grad(r, argnums=(0, 1, 2))(x, w_dw, w_pw)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# traffic accounting + autotune
# ---------------------------------------------------------------------------

def test_fused_traffic_below_staged_all_mbv2_layers():
    """The tentpole claim, asserted layer by layer: the fused pipeline's
    modeled HBM traffic is <= (strictly below) the staged two-kernel path
    for every MobileNet-V2 separable block."""
    assert len(MOBILENET_V2_SEPARABLE) == 17
    for layer, c_out in MOBILENET_V2_SEPARABLE:
        sch = get_fused_schedule(1, layer.h, layer.w, layer.c, c_out,
                                 layer.k, layer.s)
        assert sch.traffic.total_bytes < sch.staged_traffic.total_bytes, \
            (layer, c_out, sch)


def test_fused_traffic_below_staged_any_tile_h():
    """Not an autotune artifact: fused wins at every candidate tile_h too."""
    shape = SeparableShape(b=1, h=28, w=28, c_in=192, c_out=64, k=3, s=2)
    for th in (1, 2, 4, 8, 14):
        fused = fused_separable_traffic(shape, th)
        staged = staged_separable_traffic(shape, th)
        assert fused.total_bytes < staged.total_bytes, th


def test_pick_channel_block_minimizes_padding():
    """Channel blocks are what Mosaic accepts: whole 128-lane multiples of
    the 128-padded width, the widest such block up to the cap — so the
    padding is the lane cover alone, never more."""
    from repro.core.perfmodel import pick_channel_block
    for c, want in [(144, 128), (192, 128), (576, 128), (960, 128),
                    (384, 128), (128, 128), (32, 128), (8, 128)]:
        assert pick_channel_block(c) == want, (c, want)
    assert pick_channel_block(384, cap=256) == 128      # 256 leaves a tail
    assert pick_channel_block(512, cap=256) == 256
    for c in range(1, 300):
        b = pick_channel_block(c)
        assert b % 128 == 0 and 128 <= b <= 128
        # padded to the lane cover, not beyond
        assert -(-c // b) * b == -(-c // 128) * 128, (c, b)
    with pytest.raises(ValueError):
        pick_channel_block(144, cap=64)


def test_autotune_respects_vmem_budget():
    tpu = TPUConfig(vmem_bytes=2 * 1024 * 1024)
    shape = SeparableShape(b=1, h=112, w=112, c_in=96, c_out=24, k=3, s=1)
    for cand in candidate_schedules(shape, tpu):
        assert vmem_footprint_bytes(shape, cand.tile_h, tpu,
                                    cand.residency) <= tpu.vmem_bytes


@pytest.mark.parametrize("family", ["separable", "mbconv", "fusedmb"])
def test_autotune_raises_when_nothing_fits_vmem(family):
    """Where no candidate fits the budget, the solver refuses instead of
    handing the kernels a schedule the compiler would reject."""
    from repro.core.autotune import (
        VMEMInfeasibleError,
        select_fusedmb_schedule,
        select_mbconv_schedule,
    )
    from repro.core.perfmodel import MBConvShape

    tpu = TPUConfig(vmem_bytes=64 * 1024)
    mb = MBConvShape(b=1, h=56, w=56, c_in=24, c_mid=144, c_out=40, k=3,
                     s=2)
    solve = {
        "separable": lambda: select_fused_schedule(
            SeparableShape(b=1, h=56, w=56, c_in=144, c_out=24, k=3, s=1),
            tpu),
        "mbconv": lambda: select_mbconv_schedule(mb, tpu),
        "fusedmb": lambda: select_fusedmb_schedule(
            dataclasses.replace(mb, se_ratio=0.0), tpu),
    }[family]
    with pytest.raises(VMEMInfeasibleError, match="VMEM"):
        solve()


def test_autotune_selects_minimum_traffic():
    shape = SeparableShape(b=1, h=56, w=56, c_in=144, c_out=24, k=3, s=1)
    best = select_fused_schedule(shape)
    for cand in candidate_schedules(shape):
        assert best.traffic.total_bytes <= cand.traffic.total_bytes
    assert 1 <= best.tile_h <= shape.out_h
    assert best.modeled_saving > 0


def test_autotuned_schedule_runs():
    """The selected schedule is directly runnable on the kernel."""
    rng = np.random.default_rng(11)
    b, h, w_in, ci, co, s = 1, 28, 28, 96, 24, 2
    sch = get_fused_schedule(b, h, w_in, ci, co, 3, s)
    x = _rand(rng, (b, h, w_in, ci))
    w_dw = _rand(rng, (3, 3, ci))
    w_pw = _rand(rng, (ci, co))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=s,
                                 tile_h=sch.tile_h, interpret=True)
    want = _oracle(x, w_dw, w_pw, s)
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# model-layer routing
# ---------------------------------------------------------------------------

def test_separable_block_routes_both_paths():
    from repro.configs.base import ConvKernelConfig
    from repro.models.common import separable_block, separable_def
    from repro.models.param import materialize

    params = materialize(separable_def(16, 24), jax.random.key(0))
    rng = np.random.default_rng(2)
    x = _rand(rng, (2, 14, 14, 16))
    fused = separable_block(
        params, x, stride=2,
        kcfg=ConvKernelConfig(fused_separable=True, interpret=True))
    staged = separable_block(
        params, x, stride=2,
        kcfg=ConvKernelConfig(fused_separable=False, interpret=True))
    assert fused.shape == (2, 7, 7, 24)
    np.testing.assert_allclose(fused, staged, **TOL)


def test_vlm_vision_stem_forward():
    from repro.models.model import ModelConfig, forward, model_def
    from repro.models.param import materialize

    cfg = ModelConfig(name="vlm-stem", family="vlm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                      vocab=64, dtype="float32", vision_stem=True,
                      vision_stem_c0=8, vision_stem_blocks=2)
    params = materialize(model_def(cfg), jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    imgs = _rand(rng, (2, 32, 32, 3))
    logits = forward(params, {"tokens": toks, "images": imgs}, cfg)
    # 32 -> 16 (stem/2) -> 8 -> 4: 16 patch tokens prepended to 6 text tokens
    assert logits.shape == (2, 16 + 6, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
