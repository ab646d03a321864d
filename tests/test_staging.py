"""The strip-DMA staging engine: k x s x residency parity sweeps vs the
lax oracle for every fused pipeline, scratch-vs-VMEM-budget properties,
residency traffic invariants, the legacy cache-key migration, and the
sharded jitted-entry-point trace-count regression."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.autotune import (
    TPUConfig,
    candidate_mbconv_schedules,
    candidate_schedules,
    get_fused_schedule,
    mbconv_vmem_footprint_bytes,
    select_fused_schedule,
    select_mbconv_schedule,
    set_schedule_cache_dir,
    vmem_footprint_bytes,
)
from repro.core.perfmodel import (
    RESIDENCY_MODES,
    MBConvShape,
    SeparableShape,
    fused_separable_traffic,
    mbconv_fused_traffic,
    mbconv_staging_bytes,
    separable_staging_bytes,
    staging_slots,
)
from repro.core.workloads import (
    EFFICIENTNET_V2_K7_SEPARABLE,
    EFFICIENTNET_V2_K7_STEM,
)
from repro.kernels import convdk_fused_separable, convdk_mbconv_fused

TOL = dict(rtol=1e-4, atol=1e-4)


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


def _sep_oracle(x, w_dw, w_pw, stride, padding="SAME"):
    """Independent oracle: lax depthwise conv + lax.dot_general pointwise
    (NOT the repo's separable_ref)."""
    k_h, k_w, c = w_dw.shape
    dw = jax.lax.conv_general_dilated(
        x, jnp.transpose(w_dw, (2, 0, 1))[:, None],
        window_strides=(stride, stride), padding=padding,
        feature_group_count=c,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))
    return jax.lax.dot_general(
        dw, w_pw, dimension_numbers=(((3,), (0,)), ((), ())))


def _mbconv_oracle(x, w_exp, w_dw, w_se1, b_se1, w_se2, b_se2, w_proj,
                   stride, exp_act):
    """Independent oracle: explicit lax convs + explicit SE."""
    e = x @ w_exp
    if exp_act == "silu":
        e = jax.nn.silu(e)
    k_h, k_w, c_mid = w_dw.shape
    d = jax.lax.conv_general_dilated(
        e, jnp.transpose(w_dw, (2, 0, 1))[:, None],
        window_strides=(stride, stride), padding="SAME",
        feature_group_count=c_mid,
        dimension_numbers=("NHWC", "OIHW", "NHWC"))
    d = jax.nn.silu(d)
    pooled = d.mean(axis=(1, 2))
    s1 = jax.nn.silu(pooled @ w_se1 + b_se1)
    gate = jax.nn.sigmoid(s1 @ w_se2 + b_se2)
    return (d * gate[:, None, None, :]) @ w_proj


# ---------------------------------------------------------------------------
# the tentpole parity sweep: k x s x residency, every pipeline, vs lax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("residency", RESIDENCY_MODES)
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [3, 5, 7])
def test_separable_staging_parity(k, stride, residency):
    """The DMA-structured staging path (and its double-buffered variant)
    computes bit-for-bit what the resident path and the lax oracle do —
    interpret mode executes the same engine code as a TPU launch."""
    rng = np.random.default_rng(k * 10 + stride)
    x = _rand(rng, (2, 13, 11, 24))
    w_dw = _rand(rng, (k, k, 24))
    w_pw = _rand(rng, (24, 40))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=stride,
                                 interpret=True, residency=residency)
    want = _sep_oracle(x, w_dw, w_pw, stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("residency", RESIDENCY_MODES)
@pytest.mark.parametrize("mode", ["retain", "recompute"])
@pytest.mark.parametrize("k,stride", [(3, 1), (3, 2), (5, 1), (5, 2),
                                      (7, 1), (7, 2)])
def test_mbconv_staging_parity(k, stride, mode, residency):
    """Both MBConv pass-2 variants through the engine — including the
    double-buffered DMA stream of the retained DW tensor — match the lax
    oracle for k in {3, 5, 7} x s in {1, 2}."""
    rng = np.random.default_rng(k * 100 + stride * 10)
    ci, e, co = 8, 3, 16
    cm, cse = ci * e, 2
    x = _rand(rng, (1, 10, 9, ci))
    weights = (_rand(rng, (ci, cm)), _rand(rng, (k, k, cm), 0.3),
               _rand(rng, (cm, cse)), _rand(rng, (cse,), 0.1),
               _rand(rng, (cse, cm)), _rand(rng, (cm,), 0.1),
               _rand(rng, (cm, co)))
    got = convdk_mbconv_fused(x, *weights, stride=stride, mode=mode,
                              interpret=True, residency=residency)
    want = _mbconv_oracle(x, *weights, stride=stride, exp_act="silu")
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("residency", ["strip_dma", "strip_dma_db"])
def test_staging_multi_block_grids(residency):
    """DMA windows track the channel-block grid dim: >128 input channels
    (multi-ci-block reduction) and >128 output channels both stage
    correctly, including the window prefetch crossing c-block boundaries."""
    rng = np.random.default_rng(5)
    x = _rand(rng, (2, 9, 11, 130))
    w_dw = _rand(rng, (3, 3, 130))
    w_pw = _rand(rng, (130, 200))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=1, interpret=True,
                                 residency=residency)
    want = _sep_oracle(x, w_dw, w_pw, 1)
    np.testing.assert_allclose(got, want, **TOL)


def test_staging_tile_h_invariant():
    """Any tile_h gives the same numbers under DMA staging — the window
    geometry is perf-only, exactly as in the resident rendering."""
    rng = np.random.default_rng(7)
    x = _rand(rng, (1, 17, 13, 16))
    w_dw = _rand(rng, (3, 3, 16))
    w_pw = _rand(rng, (16, 24))
    want = _sep_oracle(x, w_dw, w_pw, 2)
    for tile_h in (1, 3, 8, 32):
        got = convdk_fused_separable(x, w_dw, w_pw, stride=2, tile_h=tile_h,
                                     interpret=True,
                                     residency="strip_dma_db")
        np.testing.assert_allclose(got, want, **TOL)


def test_staging_grad_flows():
    """The DMA-staged forward keeps the exact custom VJP."""
    rng = np.random.default_rng(9)
    x = _rand(rng, (1, 8, 8, 8))
    w_dw = _rand(rng, (3, 3, 8))
    w_pw = _rand(rng, (8, 8))

    def loss(res):
        def f(x_, wd_, wp_):
            return jnp.sum(convdk_fused_separable(
                x_, wd_, wp_, stride=1, interpret=True, residency=res) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(x, w_dw, w_pw)

    g_res = loss("resident")
    g_dma = loss("strip_dma_db")
    for a, b in zip(g_res, g_dma):
        np.testing.assert_allclose(a, b, **TOL)


# ---------------------------------------------------------------------------
# k=7 workload rows (EfficientNet-V2 stems)
# ---------------------------------------------------------------------------

def test_k7_workload_rows_priced_below_staged():
    """The new k=7 stem rows flow through schedule solving and keep the
    fused-below-staged invariant at full stem resolution."""
    assert [layer.k for layer in EFFICIENTNET_V2_K7_STEM] == [7, 7]
    for layer, c_out in EFFICIENTNET_V2_K7_SEPARABLE:
        sch = get_fused_schedule(1, layer.h, layer.w, layer.c, c_out,
                                 layer.k, layer.s)
        assert sch.traffic.total_bytes < sch.staged_traffic.total_bytes, \
            (layer, c_out, sch)


def test_k7_kernel_parity_vs_lax():
    """A scaled-down k=7 stem block runs the fused kernel (DMA-staged)
    against the lax oracle — the tap loop is k-generic end to end."""
    layer, c_out = EFFICIENTNET_V2_K7_SEPARABLE[0]
    rng = np.random.default_rng(77)
    x = _rand(rng, (1, 18, 18, layer.c))
    w_dw = _rand(rng, (7, 7, layer.c), 0.2)
    w_pw = _rand(rng, (layer.c, c_out))
    got = convdk_fused_separable(x, w_dw, w_pw, stride=layer.s,
                                 interpret=True, residency="strip_dma_db")
    want = _sep_oracle(x, w_dw, w_pw, layer.s)
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# traffic / scratch model invariants
# ---------------------------------------------------------------------------

def test_db_moves_same_bytes_as_single_slot():
    """Double-buffering buys overlap, not traffic: byte-identical to
    strip_dma at every (shape, tile_h), at exactly 2x the strip scratch."""
    shape = SeparableShape(b=2, h=28, w=28, c_in=144, c_out=32, k=3, s=1)
    for th in (1, 4, 8, 28):
        dma = fused_separable_traffic(shape, th, residency="strip_dma")
        db = fused_separable_traffic(shape, th, residency="strip_dma_db")
        assert dma.total_bytes == db.total_bytes
        assert dma.dma_issues == db.dma_issues > 0
        assert (separable_staging_bytes(shape, th, "strip_dma_db")
                == 2 * separable_staging_bytes(shape, th, "strip_dma"))


def test_resident_pays_full_height_refetch():
    """With more than one c_in block, the resident rendering re-reads the
    full padded height per revisiting grid cell — strictly more HBM than
    strip DMA (the honest pricing of the legacy BlockSpec path); with one
    c_in block the resident input is fetched once and can win."""
    multi = SeparableShape(b=1, h=28, w=28, c_in=192, c_out=64, k=3, s=1)
    res = fused_separable_traffic(multi, 8, residency="resident")
    dma = fused_separable_traffic(multi, 8, residency="strip_dma")
    assert res.total_bytes > dma.total_bytes
    assert res.dma_issues == 0
    single = SeparableShape(b=1, h=28, w=28, c_in=64, c_out=256, k=3, s=1)
    res1 = fused_separable_traffic(single, 4, residency="resident")
    dma1 = fused_separable_traffic(single, 4, residency="strip_dma")
    assert res1.total_bytes < dma1.total_bytes   # fetched once, reused


def test_mbconv_residency_invariants():
    shape = MBConvShape(b=1, h=14, w=14, c_in=112, c_mid=672, c_out=192,
                        k=5, s=2)
    for mode in ("retain", "recompute"):
        dma = mbconv_fused_traffic(shape, 4, mode, residency="strip_dma")
        db = mbconv_fused_traffic(shape, 4, mode, residency="strip_dma_db")
        assert dma.total_bytes == db.total_bytes
        assert dma.dma_issues == db.dma_issues > 0
        assert (mbconv_staging_bytes(shape, 4, mode, "strip_dma_db")
                == 2 * mbconv_staging_bytes(shape, 4, mode, "strip_dma"))
    # the retained-DW stream is non-overlapping: retain staging exceeds
    # recompute staging by exactly the DW slot buffers
    assert (mbconv_staging_bytes(shape, 4, "retain", "strip_dma")
            > mbconv_staging_bytes(shape, 4, "recompute", "strip_dma"))


def test_staging_slots():
    assert [staging_slots(r) for r in RESIDENCY_MODES] == [0, 1, 2]
    with pytest.raises(ValueError):
        staging_slots("vmem")
    with pytest.raises(ValueError):
        fused_separable_traffic(
            SeparableShape(b=1, h=8, w=8, c_in=8, c_out=8, k=3, s=1),
            4, residency="hbm")


# ---------------------------------------------------------------------------
# property: solved schedules never exceed the VMEM budget
# ---------------------------------------------------------------------------

sep_shape_st = st.builds(
    SeparableShape,
    b=st.sampled_from([1, 2, 8]),
    h=st.sampled_from([7, 14, 28, 56, 112]),
    w=st.sampled_from([7, 14, 28, 56, 112]),
    c_in=st.sampled_from([8, 24, 96, 144, 192, 576, 960]),
    c_out=st.sampled_from([8, 24, 64, 160, 320]),
    k=st.sampled_from([3, 5, 7]),
    s=st.sampled_from([1, 2]),
)


@given(shape=sep_shape_st)
@settings(max_examples=120, deadline=None)
def test_separable_scratch_never_exceeds_budget(shape):
    """Property: every feasible candidate's modeled staging scratch — and
    its whole VMEM footprint — fits the autotuner's budget, and the
    winning schedule is among the candidates it was solved from."""
    tpu = TPUConfig(vmem_bytes=4 * 1024 * 1024)
    cands = candidate_schedules(shape, tpu)
    assert cands
    for cand in cands:
        fp = vmem_footprint_bytes(shape, cand.tile_h, tpu, cand.residency)
        assert fp <= tpu.vmem_bytes, cand
        assert separable_staging_bytes(
            shape, cand.tile_h, cand.residency, tpu.c_block) <= fp
    best = select_fused_schedule(shape, tpu)
    assert (best.tile_h, best.residency) in {
        (c.tile_h, c.residency) for c in cands}


mbconv_shape_st = st.builds(
    MBConvShape,
    b=st.sampled_from([1, 4]),
    h=st.sampled_from([7, 14, 28, 56]),
    w=st.sampled_from([7, 14, 28, 56]),
    c_in=st.sampled_from([16, 40, 112, 192]),
    c_mid=st.sampled_from([96, 240, 672, 1152]),
    c_out=st.sampled_from([16, 40, 112, 320]),
    k=st.sampled_from([3, 5, 7]),
    s=st.sampled_from([1, 2]),
)


@given(shape=mbconv_shape_st)
@settings(max_examples=80, deadline=None)
def test_mbconv_scratch_never_exceeds_budget(shape):
    tpu = TPUConfig(vmem_bytes=8 * 1024 * 1024)
    cands = candidate_mbconv_schedules(shape, tpu)
    assert cands
    for cand in cands:
        fp = mbconv_vmem_footprint_bytes(shape, cand.tile_h, tpu,
                                         cand.residency, cand.mode)
        assert fp <= tpu.vmem_bytes, cand
        assert mbconv_staging_bytes(
            shape, cand.tile_h, cand.mode, cand.residency,
            tpu.c_block) <= fp
    best = select_mbconv_schedule(shape, tpu)
    assert best.residency in RESIDENCY_MODES


# ---------------------------------------------------------------------------
# cache-key migration: legacy entries keep outranking model picks
# ---------------------------------------------------------------------------

def test_legacy_cache_entries_survive_residency_migration(tmp_path):
    """A measured entry persisted BEFORE the residency axis (and even
    before the mesh axis) must still be honored: its tile_h wins, and the
    residency is re-solved at that tile_h instead of orphaned."""
    from repro.core.autotune import _sep_key

    shape = SeparableShape(b=1, h=28, w=28, c_in=96, c_out=24, k=3, s=1)
    new_key = _sep_key(shape, TPUConfig())
    assert "|res=auto|" in new_key
    pre_res_key = new_key.replace("|res=auto|", "|")       # 6-segment era
    pre_mesh_key = pre_res_key.replace("|mesh1x1|", "|")   # 5-segment era
    for legacy_key in (pre_res_key, pre_mesh_key):
        (tmp_path / "legacy").mkdir(exist_ok=True)
        cache_file = tmp_path / "legacy" / "convdk_schedules.json"
        cache_file.write_text(json.dumps({
            "version": 1,
            "entries": {legacy_key: {"tile_h": 2, "source": "measured"}},
        }))
        try:
            set_schedule_cache_dir(tmp_path / "legacy")
            sch = get_fused_schedule(1, 28, 28, 96, 24, 3, 1)
            assert sch.tile_h == 2, legacy_key       # measured pick honored
            assert sch.residency in RESIDENCY_MODES  # re-solved, not stale
        finally:
            set_schedule_cache_dir(None)


def test_pinned_mbconv_mode_solves_under_that_mode():
    """A pinned pass-2 mode must re-solve tile_h/residency under ITS OWN
    VMEM footprint (retain carries the retained-DW stream buffers the
    recompute winner never paid for) and must not echo the free-solve's
    cached entry."""
    from repro.core.autotune import get_mbconv_schedule

    set_schedule_cache_dir(None)
    tpu = TPUConfig(vmem_bytes=3 * 1024 * 1024)
    kwargs = dict(b=1, h=56, w=56, c_in=24, c_mid=144, c_out=40, k=5, s=2,
                  tpu=tpu)
    free = get_mbconv_schedule(**kwargs)
    for mode in ("retain", "recompute"):
        pinned = get_mbconv_schedule(**kwargs, mode=mode)
        assert pinned.mode == mode
        fp = mbconv_vmem_footprint_bytes(
            MBConvShape(b=1, h=56, w=56, c_in=24, c_mid=144, c_out=40,
                        k=5, s=2),
            pinned.tile_h, tpu, pinned.residency, mode)
        assert fp <= tpu.vmem_bytes, (mode, pinned)
    # the free-solve entry is still intact after the pinned lookups
    again = get_mbconv_schedule(**kwargs)
    assert (again.tile_h, again.mode, again.residency) \
        == (free.tile_h, free.mode, free.residency)


def test_pinned_residency_gets_its_own_cache_entry():
    """Pinned and auto requests never collide: each residency pin solves
    (and caches) under its own key and returns schedules at that pin."""
    set_schedule_cache_dir(None)
    auto = get_fused_schedule(1, 56, 56, 144, 32, 3, 1)
    for res in RESIDENCY_MODES:
        pinned = get_fused_schedule(1, 56, 56, 144, 32, 3, 1, residency=res)
        assert pinned.residency == res
    # the auto entry was not clobbered by the pins
    again = get_fused_schedule(1, 56, 56, 144, 32, 3, 1)
    assert (again.tile_h, again.residency) == (auto.tile_h, auto.residency)


# ---------------------------------------------------------------------------
# sharded jitted entry points: no re-trace at serving rate
# ---------------------------------------------------------------------------

def test_sharded_entry_point_traces_once():
    """ROADMAP edge: the sharded wrappers used to rebuild the shard_map
    closure per call, re-tracing the whole fused pipeline at serving rate.
    The cached jitted entry must trace ONCE per (mesh, schedule, shapes)."""
    from repro.compat import make_mesh
    from repro.kernels import (
        convdk_fused_separable_sharded, convdk_mbconv_fused_sharded,
    )
    from repro.kernels.convdk_sharded import TRACE_COUNTS

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(3)
    x = _rand(rng, (2, 10, 10, 16))
    w_dw = _rand(rng, (3, 3, 16))
    w_pw = _rand(rng, (16, 8))

    first = convdk_fused_separable_sharded(
        x, w_dw, w_pw, mesh=mesh, stride=1, interpret=True,
        residency="strip_dma_db")
    base = TRACE_COUNTS["separable"]
    for _ in range(3):
        out = convdk_fused_separable_sharded(
            x, w_dw, w_pw, mesh=mesh, stride=1, interpret=True,
            residency="strip_dma_db")
    assert TRACE_COUNTS["separable"] == base, "sharded separable re-traced"
    np.testing.assert_allclose(out, first, **TOL)
    np.testing.assert_allclose(out, _sep_oracle(x, w_dw, w_pw, 1), **TOL)

    ci, cm, cse, co = 8, 16, 2, 8
    weights = (_rand(rng, (ci, cm)), _rand(rng, (3, 3, cm), 0.3),
               _rand(rng, (cm, cse)), _rand(rng, (cse,), 0.1),
               _rand(rng, (cse, cm)), _rand(rng, (cm,), 0.1),
               _rand(rng, (cm, co)))
    xm = _rand(rng, (2, 8, 8, ci))
    first = convdk_mbconv_fused_sharded(
        xm, *weights, mesh=mesh, stride=1, interpret=True)
    base = TRACE_COUNTS["mbconv"]
    for _ in range(3):
        out = convdk_mbconv_fused_sharded(
            xm, *weights, mesh=mesh, stride=1, interpret=True)
    assert TRACE_COUNTS["mbconv"] == base, "sharded mbconv re-traced"
    np.testing.assert_allclose(out, first, **TOL)


def test_sharded_entry_point_retraces_on_new_schedule():
    """Distinct static schedules are distinct entries — no stale reuse."""
    from repro.compat import make_mesh
    from repro.kernels import convdk_fused_separable_sharded
    from repro.kernels.convdk_sharded import TRACE_COUNTS

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(4)
    x = _rand(rng, (1, 9, 9, 8))
    w_dw = _rand(rng, (3, 3, 8))
    w_pw = _rand(rng, (8, 8))
    convdk_fused_separable_sharded(x, w_dw, w_pw, mesh=mesh, tile_h=2,
                                   interpret=True)
    base = TRACE_COUNTS["separable"]
    out = convdk_fused_separable_sharded(x, w_dw, w_pw, mesh=mesh, tile_h=3,
                                         interpret=True)
    assert TRACE_COUNTS["separable"] > base
    np.testing.assert_allclose(out, _sep_oracle(x, w_dw, w_pw, 1), **TOL)
