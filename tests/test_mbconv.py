"""Two-pass fused MBConv kernel vs the pure jax.lax reference, the
retain/recompute traffic model, the autotuned schedule layer, and the
EfficientNet-B0 builder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.autotune import (
    TPUConfig,
    candidate_mbconv_schedules,
    get_mbconv_schedule,
    mbconv_vmem_footprint_bytes,
    select_mbconv_schedule,
)
from repro.core.perfmodel import (
    MBCONV_MODES,
    MBConvShape,
    mbconv_best_fused_traffic,
    mbconv_staged_traffic,
)
from repro.core.workloads import EFFICIENTNET_B0, EFFICIENTNET_B0_MBCONV
from repro.kernels import convdk_mbconv_fused, convdk_mbconv_staged

TOL = dict(rtol=1e-4, atol=1e-4)
IDENTITY_EXPAND = "kernels.mbconv.identity_expand"


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


def _mbconv_params(rng, c_in, expand, c_out, k, se_ratio=0.25):
    """Weights of one block; expansion ratio 1 has no expand weight."""
    c_mid = c_in * expand
    c_se = max(1, int(c_in * se_ratio))
    if expand == 1:
        w_exp, exp_act = None, None
    else:
        w_exp, exp_act = _rand(rng, (c_in, c_mid)), "silu"
    return (w_exp, _rand(rng, (k, k, c_mid), 0.3),
            _rand(rng, (c_mid, c_se)), _rand(rng, (c_se,), 0.1),
            _rand(rng, (c_se, c_mid)), _rand(rng, (c_mid,), 0.1),
            _rand(rng, (c_mid, c_out))), exp_act


def _oracle(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj, stride,
            exp_act="silu"):
    """Independent oracle: explicit lax convs + explicit SE (NOT the repo's
    mbconv_ref).  ``w_exp=None`` skips the expand, ``w_se1=None`` the SE."""
    e = x if w_exp is None else x @ w_exp
    if exp_act == "silu":
        e = jax.nn.silu(e)
    k_h, k_w, c_mid = w_dw.shape
    d = jax.lax.conv_general_dilated(
        e, jnp.transpose(w_dw, (2, 0, 1))[:, None],
        window_strides=(stride, stride), padding="SAME",
        feature_group_count=c_mid,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))
    d = jax.nn.silu(d)
    if w_se1 is not None:
        gate = jax.nn.sigmoid(
            jax.nn.silu(d.mean(axis=(1, 2)) @ w_se1 + b_se1) @ w_se2 + b_se2)
        d = d * gate[:, None, None, :]
    return d @ w_proj


# ---------------------------------------------------------------------------
# numerics vs the lax + explicit-SE oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("mode", ["retain", "recompute"])
def test_mbconv_fused_matches_lax_oracle(k, stride, mode):
    rng = np.random.default_rng(k * 10 + stride)
    b, h, w_in, ci, e, co = 2, 15, 11, 8, 3, 16      # odd H, odd W
    x = _rand(rng, (b, h, w_in, ci))
    weights, exp_act = _mbconv_params(rng, ci, e, co, k)
    got = convdk_mbconv_fused(x, *weights, stride=stride, mode=mode,
                              tile_h=4, interpret=True)
    want = _oracle(x, *weights, stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("c", [16, 136], ids=["c16", "c136"])
@pytest.mark.parametrize("se", [True, False], ids=["se", "nose"])
@pytest.mark.parametrize("mode", MBCONV_MODES)
@pytest.mark.parametrize("stride", [1, 2])
def test_mbconv_expand_ratio_one(stride, mode, se, c):
    """MBConv1 (no expansion conv): ``w_exp=None`` runs the depthwise taps
    straight on the staged input window.  136 channels give two channel
    blocks, so the window's block must follow the c_mid grid dim."""
    rng = np.random.default_rng(5)
    x = _rand(rng, (1, 9, 9, c))
    weights, exp_act = _mbconv_params(rng, c, 1, c, 3)
    assert weights[0] is None and exp_act is None
    if not se:
        weights = (*weights[:2], None, None, None, None, weights[-1])
    got = convdk_mbconv_fused(x, *weights, stride=stride, mode=mode,
                              tile_h=4, exp_act=None, interpret=True)
    want = _oracle(x, *weights, stride, exp_act=None)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


def test_mbconv_square_w_exp_still_expands():
    """A square ``w_exp`` a caller passes runs as a real expand (not the
    no-expand path), and the identity there gives the no-expand result."""
    from repro.core import telemetry

    rng = np.random.default_rng(6)
    c = 16
    x = _rand(rng, (1, 9, 9, c))
    weights, _ = _mbconv_params(rng, c, 1, c, 3)
    square = (2.0 * jnp.eye(c, dtype=jnp.float32), *weights[1:])
    t = telemetry.get_telemetry()
    before = t.get(IDENTITY_EXPAND)
    got = convdk_mbconv_fused(x, *square, stride=1, mode="recompute",
                              tile_h=4, exp_act=None, interpret=True)
    assert t.get(IDENTITY_EXPAND) == before
    np.testing.assert_allclose(got, _oracle(x, *square, 1, exp_act=None),
                               **TOL)


def test_mbconv_retain_recompute_agree():
    """Both pass-2 variants compute the identical block (schedule is
    traffic-only, like tile_h)."""
    rng = np.random.default_rng(7)
    x = _rand(rng, (2, 14, 14, 8))
    weights, _ = _mbconv_params(rng, 8, 4, 24, 5)
    for tile_h in (1, 3, 8):
        a = convdk_mbconv_fused(x, *weights, stride=2, mode="retain",
                                tile_h=tile_h, interpret=True)
        b = convdk_mbconv_fused(x, *weights, stride=2, mode="recompute",
                                tile_h=tile_h, interpret=True)
        np.testing.assert_allclose(a, b, **TOL)


def test_mbconv_fused_matches_staged_pipeline():
    """The two-pass fused kernel and the staged DW->HBM->SE->PW path are
    the same math."""
    rng = np.random.default_rng(9)
    x = _rand(rng, (2, 13, 12, 16))
    weights, _ = _mbconv_params(rng, 16, 2, 24, 3)
    for s in (1, 2):
        fused = convdk_mbconv_fused(x, *weights, stride=s, interpret=True)
        staged = convdk_mbconv_staged(x, *weights, stride=s, interpret=True)
        np.testing.assert_allclose(fused, staged, **TOL)


def test_mbconv_b0_layer_shapes_parity():
    """Acceptance gate: the fused two-pass kernel matches the lax reference
    for EVERY EfficientNet-B0 layer topology (channel-scaled so interpret
    mode stays fast; k, s, expand ratio, SE ratio and the channel-block
    structure are the real ones)."""
    rng = np.random.default_rng(11)
    seen = set()
    for ci, co, e, k, s, hw in EFFICIENTNET_B0_MBCONV:
        topo = (ci, co, e, k, s)
        if topo in seen:            # repeated stage-interior blocks
            continue
        seen.add(topo)
        ci_s, co_s = max(8, ci // 8), max(8, co // 8)
        hw_s = min(hw, 14)
        x = _rand(rng, (1, hw_s, hw_s, ci_s))
        weights, exp_act = _mbconv_params(rng, ci_s, e, co_s, k)
        sch = get_mbconv_schedule(1, hw_s, hw_s, ci_s, ci_s * e, co_s, k, s)
        got = convdk_mbconv_fused(x, *weights, stride=s, tile_h=sch.tile_h,
                                  mode=sch.mode, exp_act=exp_act,
                                  interpret=True)
        want = _oracle(x, *weights, s, exp_act=exp_act)
        np.testing.assert_allclose(got, want, err_msg=str(topo), **TOL)


def test_mbconv_grad_matches_reference():
    """Gradients through the kernels' VJP match the reference's, with an
    expand and without one (``w_exp=None``, whose cotangent is None)."""
    from repro.kernels import mbconv_ref

    rng = np.random.default_rng(3)
    x = _rand(rng, (1, 10, 9, 8))

    def loss(fn):
        return lambda *p: (fn(*p) ** 2).sum()

    for expand, exp_act in ((3, "silu"), (1, None)):
        weights, _ = _mbconv_params(rng, 8, expand, 12, 3)
        f = loss(lambda *p: convdk_mbconv_fused(
            *p, stride=2, mode="retain", exp_act=exp_act, interpret=True))
        r = loss(lambda *p: mbconv_ref(*p, stride=2, exp_act=exp_act))
        g = jax.grad(f, argnums=tuple(range(8)))(x, *weights)
        g_ref = jax.grad(r, argnums=tuple(range(8)))(x, *weights)
        assert (g[1] is None) == (expand == 1)
        for got, want in zip(g, g_ref):
            if want is None:
                assert got is None
                continue
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# two-pass traffic model + autotune
# ---------------------------------------------------------------------------

def test_mbconv_traffic_below_staged_all_b0_layers():
    """The tentpole claim, asserted layer by layer: the two-pass fused
    pipeline's modeled HBM traffic is strictly below the staged
    DW->HBM->SE->PW baseline for every EfficientNet-B0 MBConv block."""
    assert len(EFFICIENTNET_B0_MBCONV) == 16
    modes = set()
    for ci, co, e, k, s, hw in EFFICIENTNET_B0_MBCONV:
        sch = get_mbconv_schedule(1, hw, hw, ci, ci * e, co, k, s)
        assert sch.traffic.total_bytes < sch.staged_traffic.total_bytes, \
            (ci, co, e, k, s, hw, sch)
        modes.add(sch.mode)
    # B0 exercises BOTH sides of the retain/recompute crossover
    assert modes == set(MBCONV_MODES)


def _shape(c_in, e, hw, k, s, c_out):
    return MBConvShape(b=1, h=hw, w=hw, c_in=c_in, c_mid=c_in * e,
                       c_out=c_out, k=k, s=s)


mbconv_shape_st = st.builds(
    _shape,
    c_in=st.sampled_from([8, 16, 24, 32, 48, 64, 96, 128]),
    e=st.sampled_from([1, 4, 6]),
    hw=st.integers(7, 64),
    k=st.sampled_from([3, 5]),
    s=st.sampled_from([1, 2]),
    c_out=st.sampled_from([8, 16, 24, 40, 64, 96]),
)


@given(shape=mbconv_shape_st)
@settings(max_examples=150, deadline=None)
def test_mbconv_schedule_choice_never_exceeds_staged(shape):
    """Property: the autotuned (tile_h, mode) choice is (a) the cheaper of
    the VMEM-feasible retain/recompute modes at its tile_h, (b) minimal
    over all candidates, and (c) strictly below the staged baseline."""
    sch = select_mbconv_schedule(shape)
    tpu = TPUConfig()
    mode, best = mbconv_best_fused_traffic(shape, sch.tile_h,
                                           residency=sch.residency)
    if mbconv_vmem_footprint_bytes(shape, sch.tile_h, tpu, sch.residency,
                                   mode) <= tpu.vmem_bytes:
        assert sch.traffic.total_bytes == best.total_bytes
    else:
        assert sch.mode != mode
    for cand in candidate_mbconv_schedules(shape):
        assert sch.traffic.total_bytes <= cand.traffic.total_bytes
    assert sch.traffic.total_bytes < sch.staged_traffic.total_bytes
    assert 1 <= sch.tile_h <= shape.out_h
    assert sch.mode in MBCONV_MODES


def test_mbconv_best_mode_below_staged_any_tile_h():
    """On a representative high-resolution block the two-pass win is not an
    autotune artifact: the cheaper mode beats staged at EVERY candidate
    tile_h.  (Deep 7x7 blocks DO lose at deliberately bad tile_h — the
    per-layer schedule solve is load-bearing there, which is the point of
    ``select_mbconv_schedule``.)"""
    shape = _shape(16, 6, 112, 3, 2, 24)
    for tile_h in (1, 2, 4, 8, 16, 32):
        tile_h = max(1, min(tile_h, shape.out_h))
        _, best = mbconv_best_fused_traffic(shape, tile_h)
        staged = mbconv_staged_traffic(shape, tile_h)
        assert best.total_bytes < staged.total_bytes, tile_h


def test_mbconv_retain_recompute_crossover_structure():
    """Retain wins when the DW tensor is small vs the re-staged input
    (deep, low-resolution layers); recompute wins when re-reading input
    strips is cheaper than a DW round-trip (wide, high-resolution
    layers)."""
    deep = _shape(192, 6, 7, 5, 1, 192)     # 7x7x1152 tail
    wide = _shape(16, 6, 112, 3, 2, 24)     # 112x112x96 head
    assert select_mbconv_schedule(deep).mode == "retain"
    assert select_mbconv_schedule(wide).mode == "recompute"


def test_mbconv_autotune_respects_vmem_budget():
    tpu = TPUConfig(vmem_bytes=2 * 1024 * 1024)
    shape = _shape(16, 6, 56, 3, 1, 24)
    for cand in candidate_mbconv_schedules(shape, tpu):
        assert mbconv_vmem_footprint_bytes(
            shape, cand.tile_h, tpu, cand.residency, cand.mode) \
            <= tpu.vmem_bytes


def test_mbconv_autotuned_schedule_runs():
    """The selected (tile_h, mode) is directly runnable on the kernel."""
    rng = np.random.default_rng(13)
    ci, e, co, k, s, hw = 16, 4, 24, 5, 2, 14
    sch = get_mbconv_schedule(1, hw, hw, ci, ci * e, co, k, s)
    x = _rand(rng, (1, hw, hw, ci))
    weights, _ = _mbconv_params(rng, ci, e, co, k)
    got = convdk_mbconv_fused(x, *weights, stride=s, tile_h=sch.tile_h,
                              mode=sch.mode, interpret=True)
    want = _oracle(x, *weights, s)
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# model layer: mbconv_block, EfficientNet-B0, VLM stem
# ---------------------------------------------------------------------------

def test_mbconv_block_routes_both_paths_and_residual():
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import mbconv_block, mbconv_def
    from repro.models.param import materialize

    params = materialize(mbconv_def(16, 16, k=3, expand_ratio=4),
                         jax.random.key(0))
    rng = np.random.default_rng(2)
    x = _rand(rng, (2, 14, 14, 16))
    fused = mbconv_block(
        params, x, stride=1,
        kcfg=ConvKernelConfig(fused_mbconv=True, interpret=True))
    staged = mbconv_block(
        params, x, stride=1,
        kcfg=ConvKernelConfig(fused_mbconv=False, interpret=True))
    assert fused.shape == (2, 14, 14, 16)
    np.testing.assert_allclose(fused, staged, **TOL)
    # the identity residual is live: zeroing the projection leaves x
    zeroed = dict(params, proj=jnp.zeros_like(params["proj"]))
    out = mbconv_block(
        zeroed, x, stride=1,
        kcfg=ConvKernelConfig(fused_mbconv=True, interpret=True))
    np.testing.assert_allclose(out, x, **TOL)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "staged"])
def test_chained_blocks_keep_the_lane_tail_zero(fused):
    """Two chained blocks in the lane layout the network carries — a
    stride-2 block into one with the identity residual — give the narrow
    chain's numbers with a tail that stays exactly zero from block to
    block (the residual adds zero to zero), on the fused kernels and on
    the staged baseline alike."""
    from repro.configs.base import ConvKernelConfig
    from repro.kernels.common import to_lanes
    from repro.models.mbconv import mbconv_block, mbconv_def
    from repro.models.param import materialize

    cfg = ConvKernelConfig(fused_mbconv=fused, interpret=True)
    p1 = materialize(mbconv_def(16, 24, k=3, expand_ratio=6),
                     jax.random.key(3))
    p2 = materialize(mbconv_def(24, 24, k=5, expand_ratio=6),
                     jax.random.key(4))
    x = _rand(np.random.default_rng(9), (2, 15, 15, 16))

    def chain(a):
        a, _ = mbconv_block(a, p1, stride=2, cfg=cfg)
        mid = a
        a, _ = mbconv_block(a, p2, stride=1, cfg=cfg)
        return mid, a

    mid_n, out_n = chain(x)
    mid_l, out_l = chain(to_lanes(x))
    assert out_n.shape == (2, 8, 8, 24) and out_l.shape == (2, 8, 8, 128)
    for narrow, lanes in ((mid_n, mid_l), (out_n, out_l)):
        np.testing.assert_allclose(lanes[..., :24], narrow, **TOL)
        assert not bool(jnp.any(lanes[..., 24:]))
    # the residual is live in the lane layout too
    zeroed = dict(p2, proj=jnp.zeros_like(p2["proj"]))
    out, _ = mbconv_block(mid_l, zeroed, stride=1, cfg=cfg)
    np.testing.assert_array_equal(out, mid_l)


def test_lane_layout_grad_matches_narrow():
    """The custom VJP through a lane-layout input and output is the
    narrow block's gradient: the zero tail takes no cotangent into the
    weights, and the input's tail gets none."""
    from repro.kernels.common import to_lanes

    rng = np.random.default_rng(13)
    ws, exp_act = _mbconv_params(rng, 16, 4, 24, 3)
    x = _rand(rng, (1, 9, 9, 16))
    kw = dict(stride=2, tile_h=2, mode="recompute", exp_act=exp_act,
              interpret=True, residency="strip_dma_db")

    def loss(fn_x, *w):
        return (convdk_mbconv_fused(fn_x, *w, **kw)[..., :24] ** 2).sum()

    g_n = jax.grad(loss, argnums=tuple(range(8)))(x, *ws)
    g_l = jax.grad(loss, argnums=tuple(range(8)))(to_lanes(x), *ws)
    np.testing.assert_allclose(g_l[0][..., :16], g_n[0], **TOL)
    assert not bool(jnp.any(g_l[0][..., 16:]))
    for a, b in zip(g_l[1:], g_n[1:]):
        np.testing.assert_allclose(a, b, **TOL)


def test_effnet_block_specs_match_workloads_table():
    """The model builder's stage table, the workloads MBConv table and the
    paper's DW table are three views of the same network."""
    from repro.models.mbconv import EffNetConfig, effnet_block_specs

    specs = effnet_block_specs(EffNetConfig())
    assert [(sp.c_in, sp.c_out, sp.expand_ratio, sp.k, sp.s)
            for sp in specs] \
        == [t[:5] for t in EFFICIENTNET_B0_MBCONV]
    hw = 112
    for sp, layer in zip(specs, EFFICIENTNET_B0):
        assert (sp.c_mid, sp.k, sp.s) == (layer.c, layer.k, layer.s)
        assert layer.h == hw
        hw = -(-hw // sp.s)


def test_efficientnet_b0_forward_backward():
    from repro.configs.efficientnet_b0 import efficientnet_b0_smoke
    from repro.models.mbconv import efficientnet_b0_apply, efficientnet_b0_def
    from repro.models.param import materialize

    cfg = efficientnet_b0_smoke(width_mult=0.125, num_classes=4)
    params = materialize(efficientnet_b0_def(cfg), jax.random.key(0))
    rng = np.random.default_rng(0)
    x = _rand(rng, (1, 16, 16, 3))
    logits = efficientnet_b0_apply(params, x, cfg)
    assert logits.shape == (1, 4)
    assert bool(jnp.isfinite(logits).all())

    def loss_fn(p):
        return (efficientnet_b0_apply(p, x, cfg) ** 2).sum()

    grads = jax.grad(loss_fn)(params)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


def _trace_count(fn, *args):
    """``kernels.mbconv.identity_expand`` ticks while ``fn`` is traced
    (abstractly: no kernel runs).  Jit caches are cleared first so every
    launch traces anew."""
    from repro.core import telemetry

    jax.clear_caches()
    t = telemetry.get_telemetry()
    before = t.get(IDENTITY_EXPAND)
    jax.eval_shape(fn, *args)
    return t.get(IDENTITY_EXPAND) - before


@pytest.mark.parametrize("model,width,batch,hw,count", [
    ("efficientnet_b0", 0.25, 1, 32, 2),
    ("efficientnet_b0", 1.0, 256, 224, 2),
    ("mobilenet_v3_large", 0.25, 1, 32, 1),
    ("mobilenet_v3_large", 1.0, 256, 224, 1),
    ("efficientnet_v2_s", 0.25, 1, 32, 0),
])
def test_identity_expand_counter_per_trace(model, width, batch, hw, count):
    """One tick per launch with no expand stage: B0's block 0 runs pass 1
    (SE pool) and pass 2 recompute, V3-L's block 0 (no SE) pass 2 only;
    every V2-S MBConv block expands.  The full-size rows are the
    benchmark's offline cells."""
    from repro.models import mbconv as M
    from repro.models.param import abstract

    config, define, apply = {
        "efficientnet_b0": (M.EffNetConfig, M.efficientnet_b0_def,
                            M.efficientnet_b0_apply),
        "mobilenet_v3_large": (M.MobileNetV3Config, M.mobilenet_v3_def,
                               M.mobilenet_v3_apply),
        "efficientnet_v2_s": (M.EffNetV2Config, M.efficientnet_v2_s_def,
                              M.efficientnet_v2_s_apply),
    }[model]
    cfg = config(width_mult=width, num_classes=10)
    images = jax.ShapeDtypeStruct((batch, hw, hw, 3), jnp.float32)
    assert _trace_count(lambda p, x: apply(p, x, cfg),
                        abstract(define(cfg)), images) == count


def test_identity_expand_counter_silent_on_expanding_block():
    """A block whose params carry ``"exp"`` takes the expand path; the same
    block at expansion ratio 1 takes the no-expand path."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import mbconv_block, mbconv_def
    from repro.models.param import abstract

    kcfg = ConvKernelConfig(fused_mbconv=True, interpret=True)
    x = jax.ShapeDtypeStruct((2, 14, 14, 16), jnp.float32)
    for expand in (4, 1):
        params = abstract(mbconv_def(16, 16, k=3, expand_ratio=expand))
        assert ("exp" in params) == (expand > 1)
        count = _trace_count(
            lambda p, x: mbconv_block(x, p, stride=1, cfg=kcfg)[0],
            params, x)
        assert (count > 0) == (expand == 1), (expand, count)


def test_vision_stem_arch_validated():
    from repro.models.model import ModelConfig, vision_stem_def

    cfg = ModelConfig(family="vlm", vision_stem=True,
                      vision_stem_arch="MBConv")          # typo/case slip
    with pytest.raises(ValueError, match="vision_stem_arch"):
        vision_stem_def(cfg)


def test_vlm_mbconv_vision_stem_forward():
    from repro.configs.efficientnet_b0 import efficientnet_b0_vlm
    from repro.models.model import forward, model_def
    from repro.models.param import materialize

    cfg = efficientnet_b0_vlm(d_model=64, n_heads=4, n_kv_heads=4,
                              head_dim=16, d_ff=128, vocab=64,
                              dtype="float32", vision_stem_c0=8)
    assert cfg.vision_stem_arch == "mbconv"
    params = materialize(model_def(cfg), jax.random.key(0))
    assert "exp" in params["vstem"]["sep0"]          # SE-equipped MBConv stem
    assert "se_w1" in params["vstem"]["sep0"]
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    imgs = _rand(rng, (2, 32, 32, 3))
    logits = forward(params, {"tokens": toks, "images": imgs}, cfg)
    # 32 -> 16 (stem/2) -> 8 -> 4: 16 patch tokens prepended to 6 text tokens
    assert logits.shape == (2, 16 + 6, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
