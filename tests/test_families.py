"""Family-generic block stack: no-SE MBConv and MobileNet-V3 act
variants vs independent oracles, the single-pass Fused-MBConv kernel vs
a dense-conv oracle (fwd + grad), the se=off collective contract, the
fusedmb pass-split property, and the MobileNet-V3-Large /
EfficientNet-V2-S models end to end through the family-generic network
solver — sequential-oracle parity single-device and sharded."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotune import (
    BlockRow,
    get_fusedmb_schedule,
    get_mbconv_schedule,
)
from repro.core.perfmodel import (
    MBConvShape,
    fusedmb_pass_traffic,
)
from repro.kernels import (
    convdk_fusedmb_fused,
    convdk_fusedmb_staged,
    convdk_mbconv_fused,
)

TOL = dict(rtol=1e-4, atol=1e-4)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HAVE_8 = jax.device_count() >= 8


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


def _act(x, name):
    if name is None:
        return x
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "hard_swish": jax.nn.hard_swish, "sigmoid": jax.nn.sigmoid,
            "hard_sigmoid": jax.nn.hard_sigmoid}[name](x)


def _dw(x, w_dw, stride):
    k_h, k_w, c_mid = w_dw.shape
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w_dw, (2, 0, 1))[:, None],
        window_strides=(stride, stride), padding="SAME",
        feature_group_count=c_mid,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def _mbconv_oracle(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                   stride, exp_act="silu", dw_act="silu", se_act="silu",
                   gate_act="sigmoid"):
    """Independent MBConv oracle (explicit lax convs, explicit optional
    SE — NOT the repo's mbconv_ref), covering the family axes: per-block
    act, no-SE when ``w_se1 is None``, V3's (relu, hard_sigmoid) SE."""
    d = _act(_dw(_act(x @ w_exp, exp_act), w_dw, stride), dw_act)
    if w_se1 is not None:
        gate = _act(_act(d.mean(axis=(1, 2)) @ w_se1 + b_se1, se_act)
                    @ w_se2 + b_se2, gate_act)
        d = d * gate[:, None, None, :]
    return d @ w_proj


def _fusedmb_oracle(x, w_conv, w_proj, stride, act="silu"):
    """Independent Fused-MBConv oracle: ONE dense lax conv, act,
    projection einsum (NOT the repo's fusedmb_ref).  ``w_proj=None`` is
    the published expansion-1 block: the conv goes straight to the output
    channels and act(conv) is the block."""
    e = jax.lax.conv_general_dilated(
        x, w_conv, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if w_proj is None:
        return _act(e, act)
    return jnp.einsum("bhwc,cd->bhwd", _act(e, act), w_proj)


# ---------------------------------------------------------------------------
# kernel numerics: the family axes vs independent oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("mode", ["retain", "recompute"])
def test_mbconv_no_se_matches_oracle(k, stride, mode):
    """se=off (ALL SE weights None): the pool, both FCs and the gate
    disappear from the two-pass kernel, matching the SE-less oracle."""
    rng = np.random.default_rng(k * 10 + stride)
    b, h, w_in, ci, e, co = 2, 13, 11, 8, 3, 16
    x = _rand(rng, (b, h, w_in, ci))
    w_exp = _rand(rng, (ci, ci * e))
    w_dw = _rand(rng, (k, k, ci * e), 0.3)
    w_proj = _rand(rng, (ci * e, co))
    got = convdk_mbconv_fused(x, w_exp, w_dw, None, None, None, None,
                              w_proj, stride=stride, mode=mode, tile_h=4,
                              interpret=True)
    want = _mbconv_oracle(x, w_exp, w_dw, None, None, None, None, w_proj,
                          stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("mode", ["retain", "recompute"])
def test_mbconv_v3_flavor_matches_oracle(k, stride, mode):
    """MobileNet-V3's late stages: hard_swish expand/DW with the (relu,
    hard_sigmoid) SE MLP, against the explicit oracle."""
    rng = np.random.default_rng(k * 100 + stride)
    b, h, w_in, ci, e, co = 2, 11, 9, 8, 2, 12
    c_mid, c_se = ci * e, max(1, ci // 4)
    x = _rand(rng, (b, h, w_in, ci))
    weights = (_rand(rng, (ci, c_mid)), _rand(rng, (k, k, c_mid), 0.3),
               _rand(rng, (c_mid, c_se)), _rand(rng, (c_se,), 0.1),
               _rand(rng, (c_se, c_mid)), _rand(rng, (c_mid,), 0.1),
               _rand(rng, (c_mid, co)))
    got = convdk_mbconv_fused(
        x, *weights, stride=stride, mode=mode, tile_h=4,
        exp_act="hard_swish", dw_act="hard_swish", se_act="relu",
        gate_act="hard_sigmoid", interpret=True)
    want = _mbconv_oracle(x, *weights, stride, exp_act="hard_swish",
                          dw_act="hard_swish", se_act="relu",
                          gate_act="hard_sigmoid")
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("stride", [1, 2])
def test_fusedmb_matches_dense_conv_oracle(k, stride):
    """The single-pass Fused-MBConv kernel == dense conv -> act ->
    projection, and the staged baseline computes the identical block;
    with ``w_proj=None`` (expansion 1) both == dense conv -> act."""
    rng = np.random.default_rng(k + stride)
    b, h, w_in, ci, cm, co = 2, 13, 11, 8, 24, 16
    x = _rand(rng, (b, h, w_in, ci))
    w_conv = _rand(rng, (k, k, ci, cm), 0.3)
    for w_proj in (_rand(rng, (cm, co)), None):
        want = _fusedmb_oracle(x, w_conv, w_proj, stride)
        assert want.shape[-1] == (cm if w_proj is None else co)
        for tile_h in (1, 4):
            got = convdk_fusedmb_fused(x, w_conv, w_proj, stride=stride,
                                       tile_h=tile_h, interpret=True)
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, **TOL)
        staged = convdk_fusedmb_staged(x, w_conv, w_proj, stride=stride,
                                       interpret=True)
        np.testing.assert_allclose(staged, want, **TOL)


@pytest.mark.parametrize("project", [True, False], ids=["proj", "no_proj"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("c_in,per_pass", [
    (24, 5), (48, 2), (64, 2),     # V2-S's fused widths: taps pack
    (72, 1), (136, 1),             # one tap per pass: over 64, two blocks
])
def test_fusedmb_tap_packing_matches_oracle(c_in, per_pass, stride, project):
    """Tap packing: floor(128 / c_in) taps share one 128-lane contraction
    (c_in 24 -> 5, 48 and 64 -> 2) and the kernel still equals the dense
    conv oracle; c_in 72 (one tap fills the block) and 136 (two channel
    blocks) keep one tap per contraction and match too."""
    from repro.kernels.convdk_fusedmb import taps_per_pass

    assert taps_per_pass(c_in, 9) == per_pass
    rng = np.random.default_rng(c_in + stride)
    cm, co = 136, 24              # two c_mid blocks share a packing
    x = _rand(rng, (1, 9, 10, c_in))
    w_conv = _rand(rng, (3, 3, c_in, cm), 1 / np.sqrt(9 * c_in))
    w_proj = _rand(rng, (cm, co)) if project else None
    got = convdk_fusedmb_fused(x, w_conv, w_proj, stride=stride, tile_h=4,
                               interpret=True)
    np.testing.assert_allclose(
        got, _fusedmb_oracle(x, w_conv, w_proj, stride), **TOL)


# (side, stride, tile_h, c_in, project, residency): the fused kernel's
# strips that differ — the only one, first, interior, last, clipped — at
# odd and even sides and the chains' input widths (packed taps at 16, 24,
# 40; one tap a pass at 112; the width cover at 192 on 7 x 7)
FUSED_HALO_CASES = [
    (7, 1, 8, 16, False, "strip_dma_db"),
    (7, 1, 3, 192, True, "strip_dma"),
    (14, 2, 2, 24, True, "strip_dma_db"),
    (15, 1, 4, 40, True, "resident"),
    (28, 2, 4, 112, True, "strip_dma_db"),
    (56, 1, 16, 24, False, "strip_dma"),
    (113, 2, 16, 16, True, "strip_dma_db"),
]


@pytest.mark.parametrize(
    "side,stride,tile_h,c_in,project,residency", FUSED_HALO_CASES,
    ids=[f"{c[0]}x{c[0]}_s{c[1]}_t{c[2]}_c{c[3]}"
         f"{'_proj' if c[4] else ''}_{c[5]}" for c in FUSED_HALO_CASES])
def test_fusedmb_halo_in_vmem_matches_reference(side, stride, tile_h, c_in,
                                                project, residency):
    """The single-pass kernel staging the unpadded activation, its conv
    halo built in VMEM, matches ``fusedmb_ref`` and the dense-conv oracle;
    the lane-layout input gives the same numbers with a zero tail."""
    from repro.kernels.common import to_lanes
    from repro.kernels.ref import fusedmb_ref

    rng = np.random.default_rng(side * 10 + c_in)
    c_mid = 2 * c_in if project else c_in
    x = _rand(rng, (1, side, side, c_in))
    w_conv = _rand(rng, (3, 3, c_in, c_mid), 0.2)
    w_proj = _rand(rng, (c_mid, 32), 0.2) if project else None
    c_out = 32 if project else c_mid
    kw = dict(stride=stride, tile_h=tile_h, interpret=True,
              residency=residency)
    got = convdk_fusedmb_fused(x, w_conv, w_proj, **kw)
    lanes = convdk_fusedmb_fused(to_lanes(x), w_conv, w_proj, **kw)
    want = fusedmb_ref(x, w_conv, w_proj, stride=stride)
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(
        got, _fusedmb_oracle(x, w_conv, w_proj, stride), **TOL)
    assert lanes.shape == (*want.shape[:3], -(-c_out // 128) * 128)
    np.testing.assert_array_equal(lanes[..., :c_out], got)
    assert not bool(jnp.any(lanes[..., c_out:]))


def test_fused_chain_carries_the_lane_layout():
    """A fused block with no projection into an MBConv block with the
    identity residual, on the lane layout: the narrow chain's numbers,
    and a tail that is exactly zero after each block."""
    from repro.configs.base import ConvKernelConfig
    from repro.kernels.common import to_lanes
    from repro.models.mbconv import (
        fusedmb_block, fusedmb_def, mbconv_block, mbconv_def)
    from repro.models.param import materialize

    cfg = ConvKernelConfig(interpret=True)
    pf = materialize(fusedmb_def(24, 24, 24, k=3, expand_ratio=1),
                     jax.random.key(5))
    pm = materialize(mbconv_def(24, 24, k=3, expand_ratio=4),
                     jax.random.key(6))
    x = _rand(np.random.default_rng(17), (2, 14, 14, 24), 0.5)

    def chain(a):
        a, _ = fusedmb_block(a, pf, stride=1, cfg=cfg)
        mid = a
        a, _ = mbconv_block(a, pm, stride=1, cfg=cfg)
        return mid, a

    for narrow, lanes in zip(chain(x), chain(to_lanes(x))):
        assert lanes.shape == (2, 14, 14, 128)
        np.testing.assert_allclose(lanes[..., :24], narrow, **TOL)
        assert not bool(jnp.any(lanes[..., 24:]))


def test_fusedmb_grad_matches_oracle():
    """Gradients with a projection and without one (``w_proj=None``: its
    cotangent is None)."""
    rng = np.random.default_rng(17)
    x = _rand(rng, (1, 10, 9, 8))
    w_conv = _rand(rng, (3, 3, 8, 16), 0.3)

    def loss(fn):
        return lambda *p: (fn(*p) ** 2).sum()

    for w_proj in (_rand(rng, (16, 12)), None):
        f = loss(lambda x_, wc, wp: convdk_fusedmb_fused(
            x_, wc, wp, stride=2, interpret=True))
        r = loss(lambda x_, wc, wp: _fusedmb_oracle(x_, wc, wp, 2))
        g = jax.grad(f, argnums=(0, 1, 2))(x, w_conv, w_proj)
        g_ref = jax.grad(r, argnums=(0, 1, 2))(x, w_conv, w_proj)
        assert (g[2] is None) == (w_proj is None)
        for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


NO_PROJECT = "kernels.fusedmb.no_project"
TAP_PACKED = "kernels.fusedmb.tap_packed"
HALO_IN_VMEM = "kernels.staging.halo_in_vmem"


def _ticks(name, fn, *args):
    """Ticks of counter ``name`` while ``fn`` is traced (abstractly: no
    kernel runs), jit caches cleared first."""
    from repro.core import telemetry

    jax.clear_caches()
    t = telemetry.get_telemetry()
    before = t.get(name)
    jax.eval_shape(fn, *args)
    return t.get(name) - before


def test_square_w_proj_still_projects():
    """A square ``w_proj`` passed explicitly runs as a projection (and
    does not tick the no-projection counter); only ``None`` drops it."""
    rng = np.random.default_rng(23)
    x = _rand(rng, (2, 12, 10, 16))
    w_conv = _rand(rng, (3, 3, 16, 16), 0.3)
    w_proj = _rand(rng, (16, 16))
    proj = convdk_fusedmb_fused(x, w_conv, w_proj, tile_h=4, interpret=True)
    bare = convdk_fusedmb_fused(x, w_conv, None, tile_h=4, interpret=True)
    np.testing.assert_allclose(proj, _fusedmb_oracle(x, w_conv, w_proj, 1),
                               **TOL)
    np.testing.assert_allclose(bare, _fusedmb_oracle(x, w_conv, None, 1),
                               **TOL)
    assert float(jnp.abs(proj - bare).max()) > 1e-1
    f = convdk_fusedmb_fused
    assert _ticks(NO_PROJECT, lambda *a: f(*a, interpret=True),
                  x, w_conv, w_proj) == 0
    assert _ticks(NO_PROJECT,
                  lambda x_, wc: f(x_, wc, None, interpret=True),
                  x, w_conv) == 1


# EfficientNet-V2-S, arXiv:2104.00298 Table 2: (family, expansion, k,
# first stride, c_in, c_out, repeats) after the 3 x 3 / 2 stem to 24
V2S_TABLE = [
    ("fusedmb", 1, 3, 1, 24, 24, 2),
    ("fusedmb", 4, 3, 2, 24, 48, 4),
    ("fusedmb", 4, 3, 2, 48, 64, 4),
    ("mbconv", 4, 3, 2, 64, 128, 6),
    ("mbconv", 6, 3, 1, 128, 160, 9),
    ("mbconv", 6, 3, 2, 160, 256, 15),
]


def test_v2s_spec_table_and_param_tree_are_published():
    """Full-width V2-S: the spec table is the paper's row for row, the
    expansion-1 blocks (0-1) have no ``proj`` (the conv goes straight to
    24 channels), every other fused block has one, and the tree holds
    21.3 M parameters (the paper's 22 M counts batch norm too)."""
    from repro.models.mbconv import (
        EffNetV2Config, effnet_v2_block_specs, efficientnet_v2_s_def)
    from repro.models.param import abstract

    cfg = EffNetV2Config()
    want = []
    for family, e, k, s, c_in, c_out, reps in V2S_TABLE:
        for i in range(reps):
            ci = c_in if i == 0 else c_out
            cm = c_out if e == 1 else ci * e
            want.append((family, ci, cm, c_out, k, s if i == 0 else 1,
                         max(1, int(ci * 0.25)) if family == "mbconv" else 0))
    specs = effnet_v2_block_specs(cfg)
    assert [(sp.family, sp.c_in, sp.c_mid, sp.c_out, sp.k, sp.s, sp.c_se)
            for sp in specs] == want
    tree = abstract(efficientnet_v2_s_def(cfg))
    assert sorted(tree["block0"]) == sorted(tree["block1"]) == ["conv"]
    assert tree["block0"]["conv"].shape == (3, 3, 24, 24)
    for i in range(2, 10):
        assert sorted(tree[f"block{i}"]) == ["conv", "proj"], i
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == 21_304_616


def _model_ticks(name, model, width, batch, hw):
    """Ticks of counter ``name`` while ``model``'s served apply traces at
    ``width`` on a (batch, hw, hw, 3) batch of images."""
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
    return _ticks(name, lambda p, x: apply(p, x, cfg),
                  abstract(define(cfg)), images)


@pytest.mark.parametrize("model,width,batch,hw,count", [
    ("efficientnet_v2_s", 1.0, 64, 384, 2),
    ("efficientnet_v2_s", 0.25, 1, 32, 2),
    ("efficientnet_b0", 1.0, 256, 224, 0),
    ("mobilenet_v3_large", 1.0, 256, 224, 0),
])
def test_no_project_counter_per_trace(model, width, batch, hw, count):
    """One tick per launch with no projection: V2-S's blocks 0 and 1
    (the full-size row is the benchmark's cell), none in the all-MBConv
    families."""
    assert _model_ticks(NO_PROJECT, model, width, batch, hw) == count


@pytest.mark.parametrize("model,width,batch,hw,count", [
    ("efficientnet_v2_s", 1.0, 64, 384, 10),
    ("efficientnet_v2_s", 0.25, 1, 32, 10),
    ("efficientnet_b0", 1.0, 256, 224, 0),
    ("mobilenet_v3_large", 1.0, 256, 224, 0),
])
def test_tap_packed_counter_per_trace(model, width, batch, hw, count):
    """One tick per launch that packs taps: all ten V2-S fused blocks, at
    full width (c_in 24, 48, 64; the benchmark's cell) and at width 0.25
    (c_in 8 and 16), none in the all-MBConv families."""
    from repro.models.mbconv import EffNetV2Config, effnet_v2_block_specs

    if model == "efficientnet_v2_s":
        specs = effnet_v2_block_specs(
            EffNetV2Config(width_mult=width, num_classes=10))
        assert count == sum(sp.family == "fusedmb" and sp.c_in <= 64
                            for sp in specs)
    assert _model_ticks(TAP_PACKED, model, width, batch, hw) == count


@pytest.mark.parametrize("model,width,batch,hw,count", [
    ("efficientnet_b0", 1.0, 256, 224, 12),
    ("mobilenet_v3_large", 1.0, 256, 224, 13),
    ("efficientnet_v2_s", 1.0, 64, 384, 26),
    ("efficientnet_v2_s", 0.25, 1, 32, 40),
])
def test_halo_in_vmem_counter_per_trace(model, width, batch, hw, count):
    """One tick per block whose kernels stage the block's activation as
    it is: every block of the three families at their cells' sizes but
    those taking more than 128 channels at a width that is not whole
    sublanes, which keep a width cover (B0's blocks 12-15 at 7 x 7, V3-L's
    13-14 at 7 x 7, V2-S's 26-39 at 12 x 12).  At width 0.25 no V2-S block
    takes more than 128 channels."""
    from repro.models.mbconv import EffNetV2Config, effnet_v2_block_specs

    if (model, width) == ("efficientnet_v2_s", 0.25):
        specs = effnet_v2_block_specs(
            EffNetV2Config(width_mult=width, num_classes=10))
        assert count == len(specs) and max(sp.c_in for sp in specs) <= 128
    assert _model_ticks(HALO_IN_VMEM, model, width, batch, hw) == count


def test_no_project_counter_silent_on_expanding_fused_block():
    """A fused block at expansion 4 carries ``proj`` and projects; the same
    block at expansion 1 carries none and takes the no-projection path."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import fusedmb_block, fusedmb_def
    from repro.models.param import abstract

    kcfg = ConvKernelConfig(interpret=True)
    x = jax.ShapeDtypeStruct((2, 14, 14, 16), jnp.float32)
    for expand in (4, 1):
        params = abstract(fusedmb_def(16, 16, 16 * expand, k=3,
                                      expand_ratio=expand))
        assert ("proj" in params) == (expand > 1)
        count = _ticks(
            NO_PROJECT,
            lambda p, x: fusedmb_block(x, p, stride=1, cfg=kcfg)[0],
            params, x)
        assert count == (expand == 1), (expand, count)


# ---------------------------------------------------------------------------
# pass-split property + family-generic rows
# ---------------------------------------------------------------------------

def test_fusedmb_pass2_traffic_is_exactly_zero():
    """The one-pass family's pass-2 figures are identically zero at EVERY
    (shape, tile_h, residency) — the structural fact the pipeliner leans
    on when it refuses to hide a consumer behind a fusedmb block."""
    for (ci, cm, co, hw, k, s) in [(24, 24, 24, 56, 3, 1),
                                   (24, 96, 48, 56, 3, 2),
                                   (64, 256, 128, 14, 3, 2)]:
        shape = MBConvShape(b=1, h=hw, w=hw, c_in=ci, c_mid=cm, c_out=co,
                            k=k, s=s, se_ratio=0.0)
        for tile_h in (1, 4, 8):
            for res in ("resident", "strip_dma", "strip_dma_db"):
                p1, p2 = fusedmb_pass_traffic(shape, tile_h, 128, res)
                assert p2.total_bytes == 0, (shape, tile_h, res, p2)
                assert p1.total_bytes > 0
        sch = get_fusedmb_schedule(1, hw, hw, ci, cm, co, k, s)
        assert sch.total_bytes < sch.staged_total_bytes


def test_blockrow_legacy_tuple_compat():
    """Legacy 7-tuples ARE BlockRows: same positional head, mbconv/silu
    defaults — the solver accepts mixed row vocabularies."""
    r = BlockRow(56, 56, 24, 144, 40, 5, 2)
    assert (r.family, r.act, r.se_ratio) == ("mbconv", "silu", 0.25)
    f = BlockRow(56, 56, 24, 96, 24, 3, 1, family="fusedmb", act="silu",
                 se_ratio=0.25)
    assert f.se_ratio == 0.0                 # fusedmb never carries SE


def test_model_tables_match_workload_tables():
    """The model builders' spec tables and the core workload tables are
    two views of the same networks — row for row, family, act and SE
    included."""
    from repro.core.workloads import (
        effnet_v2_chain_rows, mobilenet_v3_chain_rows)
    from repro.models.mbconv import (
        EffNetV2Config, MobileNetV3Config, block_chain_rows,
        effnet_v2_block_specs, mobilenet_v3_specs)

    v3 = block_chain_rows(mobilenet_v3_specs(MobileNetV3Config()), 112, 112)
    assert v3 == mobilenet_v3_chain_rows("large")
    assert {r.act for r in v3} == {"relu", "hard_swish"}
    assert any(r.se_ratio == 0.0 for r in v3)
    assert any(r.se_ratio > 0.0 for r in v3)

    v2s = block_chain_rows(effnet_v2_block_specs(EffNetV2Config()), 112, 112)
    assert v2s == effnet_v2_chain_rows()
    assert len(v2s) == 40
    assert [r.family for r in v2s][:10] == ["fusedmb"] * 10
    assert all(r.family == "mbconv" for r in v2s[10:])


def test_family_axes_in_schedule_cache_keys():
    """act/se are schedule-cache axes: a no-SE or hard_swish solve never
    collides with the silu/se-on pick for the same layer shape."""
    base = get_mbconv_schedule(1, 14, 14, 16, 64, 24, 3, 1)
    no_se = get_mbconv_schedule(1, 14, 14, 16, 64, 24, 3, 1, se_ratio=0.0)
    hs = get_mbconv_schedule(1, 14, 14, 16, 64, 24, 3, 1, act="hard_swish")
    assert base.traffic.total_bytes >= no_se.traffic.total_bytes
    assert no_se.traffic.total_bytes < base.staged_traffic.total_bytes
    assert hs.tile_h >= 1


# ---------------------------------------------------------------------------
# end-to-end: V3-Large and V2-S vs sequential oracles
# ---------------------------------------------------------------------------

def _sequential_blocks(x, specs, params):
    """Sequential oracle for the block chain: repo refs (lax math) +
    identity residuals, one block at a time — the graph path must match."""
    from repro.kernels import fusedmb_ref, mbconv_ref

    for i, sp in enumerate(specs):
        p = params[f"block{i}"]
        if sp.family == "fusedmb":
            y = fusedmb_ref(x, p["conv"], p.get("proj"), stride=sp.s,
                            act=sp.act)
        else:
            if "exp" in p:
                w_exp, exp_act = p["exp"], sp.act
            else:
                w_exp, exp_act = jnp.eye(sp.c_mid, dtype=x.dtype), None
            y = mbconv_ref(x, w_exp, p["dw"], p.get("se_w1"),
                           p.get("se_b1"), p.get("se_w2"), p.get("se_b2"),
                           p["proj"], stride=sp.s, exp_act=exp_act,
                           dw_act=sp.act, se_act=sp.se_act,
                           gate_act=sp.gate_act)
        if sp.has_residual:
            y = y + x
        x = y
    return x


def _v3_oracle(params, images, cfg):
    from repro.models.mbconv import mobilenet_v3_specs

    x = jax.lax.conv_general_dilated(
        images, params["stem"], (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = _sequential_blocks(jax.nn.hard_swish(x), mobilenet_v3_specs(cfg),
                           params)
    x = jax.nn.hard_swish(jnp.einsum("bhwc,cd->bhwd", x, params["head"]))
    x = jax.nn.hard_swish(x.mean(axis=(1, 2)) @ params["fc"])
    return x @ params["cls_w"] + params["cls_b"]


def _v2s_oracle(params, images, cfg):
    from repro.models.mbconv import effnet_v2_block_specs

    x = jax.lax.conv_general_dilated(
        images, params["stem"], (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = _sequential_blocks(jax.nn.silu(x), effnet_v2_block_specs(cfg),
                           params)
    x = jax.nn.silu(jnp.einsum("bhwc,cd->bhwd", x, params["head"]))
    return x.mean(axis=(1, 2)) @ params["cls_w"] + params["cls_b"]


def test_mobilenet_v3_matches_sequential_oracle():
    """V3-Large (width-scaled) through blockgraph == the sequential
    per-block ref loop, forward AND gradient, on the fused kernel path."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import MobileNetV3Config, mobilenet_v3_def
    from repro.models.mbconv import mobilenet_v3_apply
    from repro.models.param import materialize

    cfg = MobileNetV3Config(num_classes=4, width_mult=0.125)
    params = materialize(mobilenet_v3_def(cfg), jax.random.key(0))
    rng = np.random.default_rng(0)
    x = _rand(rng, (1, 16, 16, 3), 0.5)
    kcfg = ConvKernelConfig(interpret=True)
    logits = mobilenet_v3_apply(params, x, cfg, kcfg=kcfg)
    want = _v3_oracle(params, x, cfg)
    assert logits.shape == (1, 4)
    np.testing.assert_allclose(logits, want, **TOL)

    g = jax.grad(lambda p: (mobilenet_v3_apply(p, x, cfg, kcfg=kcfg)
                            ** 2).sum())(params)
    g_ref = jax.grad(lambda p: (_v3_oracle(p, x, cfg) ** 2).sum())(params)
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_efficientnet_v2_s_matches_sequential_oracle():
    """V2-S (truncated stages: fused head + MBConv tail) through the
    mixed-family blockgraph == the sequential ref loop, fwd + grad."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import (
        EffNetV2Config, efficientnet_v2_s_apply, efficientnet_v2_s_def)
    from repro.models.param import materialize

    cfg = EffNetV2Config(num_classes=4, width_mult=0.25, head_c=128,
                         stages=(("fusedmb", 1, 3, 1, 24, 1),
                                 ("fusedmb", 4, 3, 2, 48, 2),
                                 ("mbconv", 4, 3, 2, 64, 2)))
    params = materialize(efficientnet_v2_s_def(cfg), jax.random.key(1))
    rng = np.random.default_rng(1)
    x = _rand(rng, (1, 16, 16, 3), 0.5)
    kcfg = ConvKernelConfig(interpret=True)
    logits = efficientnet_v2_s_apply(params, x, cfg, kcfg=kcfg)
    want = _v2s_oracle(params, x, cfg)
    assert logits.shape == (1, 4)
    np.testing.assert_allclose(logits, want, **TOL)

    g = jax.grad(lambda p: (efficientnet_v2_s_apply(p, x, cfg, kcfg=kcfg)
                            ** 2).sum())(params)
    g_ref = jax.grad(lambda p: (_v2s_oracle(p, x, cfg) ** 2).sum())(params)
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _named_entries():
    """Each family's named (def, apply) entries at a CI-sized config."""
    from repro.models import mbconv as M
    return {
        "efficientnet_b0": (
            M.EffNetConfig(num_classes=4, width_mult=0.125),
            M.efficientnet_b0_def, M.efficientnet_b0_apply),
        "mobilenet_v3_large": (
            M.MobileNetV3Config(num_classes=4, width_mult=0.125),
            M.mobilenet_v3_def, M.mobilenet_v3_apply),
        "efficientnet_v2_s": (
            M.EffNetV2Config(num_classes=4, width_mult=0.25, head_c=128,
                             stages=(("fusedmb", 1, 3, 1, 24, 1),
                                     ("fusedmb", 4, 3, 2, 48, 2),
                                     ("mbconv", 4, 3, 2, 64, 2))),
            M.efficientnet_v2_s_def, M.efficientnet_v2_s_apply),
    }


@pytest.mark.parametrize("model", ["efficientnet_b0", "mobilenet_v3_large",
                                   "efficientnet_v2_s"])
def test_named_entry_is_network_apply(model):
    """Each family's named entry is the one network entry at its config:
    the same param tree as ``network_def`` and the same logits as
    ``network_apply`` with the same config and params."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import network_apply, network_def
    from repro.models.param import abstract, materialize

    cfg, define, apply = _named_entries()[model]
    assert abstract(define(cfg)) == abstract(network_def(cfg))
    params = materialize(define(cfg), jax.random.key(0))
    x = _rand(np.random.default_rng(0), (1, 16, 16, 3), 0.5)
    kcfg = ConvKernelConfig(interpret=True)
    got = apply(params, x, cfg, kcfg=kcfg)
    want = network_apply(params, x, cfg, kcfg=kcfg)
    assert got.shape == (1, 4)
    assert jnp.array_equal(got, want)


def test_one_pass_nodes_validate_and_refuse_phantom_overlap():
    """Graph contract: fusedmb nodes carry an EMPTY pass 2, validate as
    one-pass producers, and a pipelined entry directly behind one is a
    validation error (there is no pass-2 compute to hide the DMA in)."""
    from repro.models.blockgraph import (
        BlockGraph, BlockNode, GraphValidationError, fusedmb_stage_io,
        mbconv_stage_io)

    p1, p2 = fusedmb_stage_io(3)
    assert "act3" in p1.reads and "act4" in p1.writes
    assert not p2.reads and not p2.writes

    from repro.configs.base import ConvKernelConfig
    from repro.models.blockgraph import build_block_graph
    from repro.models.mbconv import (
        EffNetV2Config, effnet_v2_block_specs, efficientnet_v2_s_def)
    from repro.models.param import materialize

    cfg = EffNetV2Config(num_classes=4, width_mult=0.25, head_c=64,
                         stages=(("fusedmb", 2, 3, 1, 24, 2),))
    params = materialize(efficientnet_v2_s_def(cfg), jax.random.key(0))
    specs = effnet_v2_block_specs(cfg)
    graph = build_block_graph(specs, params,
                              kcfg=ConvKernelConfig(interpret=True))
    graph.validate()                         # one-pass chain is well-formed
    assert all(n.one_pass for n in graph.nodes)

    # a pipelined entry behind the one-pass producer must refuse
    p1b, p2b = mbconv_stage_io(1, mode="retain")
    bad = BlockGraph(nodes=(
        BlockNode(0, "fusedmb0", *fusedmb_stage_io(0)),
        BlockNode(1, "mbconv1", p1b, p2b, entry_overlap="pipelined")))
    with pytest.raises(GraphValidationError, match="single-pass"):
        bad.validate()


# ---------------------------------------------------------------------------
# sharded: the se=off collective contract + end-to-end model parity
# (8-virtual-device harness, in-process when available, else subprocess)
# ---------------------------------------------------------------------------

_PREAMBLE = textwrap.dedent("""
    import os
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat import make_mesh

    assert jax.device_count() >= 8, jax.devices()

    def rand(rng, shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    mesh = make_mesh((2, 4), ("data", "model"))
""")


def run_case(body: str) -> None:
    src = _PREAMBLE + textwrap.dedent(body)
    if HAVE_8:
        exec(compile(src, "<families-sharded-case>", "exec"),
             {"__name__": "__families_sharded__"})
        return
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.setdefault("CONVDK_RESIDUAL_BARRIER", "on")
    res = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]


def test_no_se_block_emits_zero_squeeze_collectives():
    """Intercept ``jax.lax.psum`` during the sharded se=off MBConv trace
    under the (2,4) mesh: the ONLY collective over "model" is the
    projection partial — the SE squeeze psum is GONE, in both pass-2
    modes (the modeled se=off collective saving is structural, not just
    an accounting delta)."""
    run_case("""
    from repro import compat
    from repro.kernels import convdk_mbconv_fused_sharded, mbconv_ref
    from repro.kernels.convdk_sharded import _mbconv_sharded_entry
    compat.residual_barrier_needed()
    _mbconv_sharded_entry.cache_clear()
    rng = np.random.default_rng(4)
    b, h, w_in, ci, e, co, k, s = 8, 9, 9, 8, 2, 16, 3, 1
    x = rand(rng, (b, h, w_in, ci))
    w_exp = rand(rng, (ci, ci * e))
    w_dw = rand(rng, (k, k, ci * e), 0.3)
    w_proj = rand(rng, (ci * e, co))
    weights = (w_exp, w_dw, None, None, None, None, w_proj)
    want = mbconv_ref(x, *weights, stride=s)

    calls = []
    orig_psum = jax.lax.psum

    def counting_psum(val, axis_name, **kw):
        calls.append((jnp.shape(val), axis_name))
        return orig_psum(val, axis_name, **kw)

    jax.lax.psum = counting_psum
    try:
        for mode in ("retain", "recompute"):
            calls.clear()
            got = convdk_mbconv_fused_sharded(
                x, *weights, mesh=mesh, stride=s, tile_h=3, mode=mode,
                interpret=True)
            np.testing.assert_allclose(got, want, err_msg=mode,
                                       rtol=1e-4, atol=1e-4)
            model_calls = [c for c in calls if c[1] == "model"]
            # exactly ONE model-axis collective: the projection partial.
            # ZERO squeeze psums — there is no SE pool to reduce.
            assert len(model_calls) == 1, (mode, calls)
            assert model_calls[0][0] == (b // 2, h, w_in, co), model_calls
    finally:
        jax.lax.psum = orig_psum
    print("NO_SE_ZERO_SQUEEZE_OK")
    """)


def test_sharded_models_match_single_device():
    """V3-Large and V2-S end to end on the (2,4) mesh at b=8: the
    solver-planned sharded run equals the single-device run (which the
    oracle tests above pin to the sequential refs)."""
    run_case("""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import (
        EffNetV2Config, MobileNetV3Config, efficientnet_v2_s_apply,
        efficientnet_v2_s_def, mobilenet_v3_apply, mobilenet_v3_def)
    from repro.models.param import materialize

    kcfg = ConvKernelConfig(interpret=True)
    rng = np.random.default_rng(5)
    x = rand(rng, (8, 16, 16, 3), 0.5)

    cfg = MobileNetV3Config(num_classes=4, width_mult=0.125)
    params = materialize(mobilenet_v3_def(cfg), jax.random.key(0))
    single = mobilenet_v3_apply(params, x, cfg, kcfg=kcfg)
    sharded = mobilenet_v3_apply(params, x, cfg, kcfg=kcfg, mesh=mesh)
    np.testing.assert_allclose(sharded, single, rtol=1e-4, atol=1e-4)

    v2cfg = EffNetV2Config(num_classes=4, width_mult=0.25, head_c=128,
                           stages=(("fusedmb", 1, 3, 1, 24, 1),
                                   ("fusedmb", 4, 3, 2, 48, 2),
                                   ("mbconv", 4, 3, 2, 64, 2)))
    v2params = materialize(efficientnet_v2_s_def(v2cfg), jax.random.key(1))
    single2 = efficientnet_v2_s_apply(v2params, x, v2cfg, kcfg=kcfg)
    sharded2 = efficientnet_v2_s_apply(v2params, x, v2cfg, kcfg=kcfg,
                                       mesh=mesh)
    np.testing.assert_allclose(sharded2, single2, rtol=1e-4, atol=1e-4)
    print("SHARDED_MODELS_OK")
    """)
