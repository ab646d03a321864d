"""Compile rehearsal: the ConvDK kernels compiled for a described TPU v5e.

The TPU compiler ships with the installed jaxlib and compiles for a chip
that is described, not attached, so these tests show for free what
interpret mode hides: tile alignment of blocks and DMA windows, strided
loads, mask shapes and the scoped-VMEM limit.  Every case compiles one
kernel (or the whole B0 apply) at published widths with ``interpret=False``
and asserts that the Mosaic kernel is in the program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core.autotune import (
    get_fused_schedule,
    get_fusedmb_schedule,
    get_mbconv_schedule,
)
from repro.core.workloads import EFFICIENTNET_B0_MBCONV, \
    MOBILENET_V2_SEPARABLE
from repro.kernels import (
    convdk_fused_separable,
    convdk_fusedmb_fused,
    convdk_mbconv_fused,
    convdk_mbconv_fused_sharded,
)
from repro.models.mbconv import (
    EffNetV2Config,
    MOBILENET_V3_LARGE_BLOCKS,
    effnet_v2_block_specs,
)

BATCH = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _mbconv_args(sharding, hw, c_in, c_mid, c_out, k, se=True):
    c_se = max(1, c_in // 4)
    se_args = ((_spec(sharding, c_mid, c_se), _spec(sharding, c_se),
                _spec(sharding, c_se, c_mid), _spec(sharding, c_mid))
               if se else (None,) * 4)
    return (_spec(sharding, BATCH, hw, hw, c_in),
            _spec(sharding, c_in, c_mid), _spec(sharding, k, k, c_mid),
            *se_args, _spec(sharding, c_mid, c_out))


@pytest.mark.parametrize("row", [2, 3], ids=["s1_56x144", "s2_56x144"])
def test_fused_separable_compiles(one_chip, row):
    layer, c_out = MOBILENET_V2_SEPARABLE[row]
    sch = get_fused_schedule(BATCH, layer.h, layer.w, layer.c, c_out,
                             layer.k, layer.s)
    fn = functools.partial(
        convdk_fused_separable, stride=layer.s, tile_h=sch.tile_h,
        dw_act="relu6", act=None, interpret=False, residency=sch.residency)
    _assert_kernel(fn, _spec(one_chip, BATCH, layer.h, layer.w, layer.c),
                   _spec(one_chip, layer.k, layer.k, layer.c),
                   _spec(one_chip, layer.c, c_out))


@pytest.mark.parametrize("mode", ["retain", "recompute"])
@pytest.mark.parametrize("block", [0, 3], ids=["b0_block0", "b0_block3"])
def test_mbconv_passes_compile(one_chip, block, mode):
    """B0 block 0 (112 x 112, identity expand) and block 3 (144 channels,
    5 x 5, stride 2), each pass-2 mode under its own solved schedule."""
    c_in, c_out, e, k, s, hw = EFFICIENTNET_B0_MBCONV[block]
    c_mid = c_in * e
    sch = get_mbconv_schedule(BATCH, hw, hw, c_in, c_mid, c_out, k, s,
                              mode=mode)
    fn = functools.partial(
        convdk_mbconv_fused, stride=s, tile_h=sch.tile_h, mode=mode,
        exp_act="silu" if e > 1 else None, interpret=False,
        residency=sch.residency)
    _assert_kernel(fn, *_mbconv_args(one_chip, hw, c_in, c_mid, c_out, k))


def test_mobilenet_v3_block_compiles(one_chip):
    """A V3-Large block with a c_mid (72) no 128-lane block divides:
    relu expand/DW, relu/hard_sigmoid SE, 5 x 5 stride 2 at 56 x 56."""
    c_mid, c_out, k, s, se, act = MOBILENET_V3_LARGE_BLOCKS[3]
    c_in, hw = MOBILENET_V3_LARGE_BLOCKS[2][1], 56
    assert (c_mid, se) == (72, True)
    sch = get_mbconv_schedule(BATCH, hw, hw, c_in, c_mid, c_out, k, s,
                              act=act)
    fn = functools.partial(
        convdk_mbconv_fused, stride=s, tile_h=sch.tile_h, mode=sch.mode,
        exp_act=act, dw_act=act, se_act="relu", gate_act="hard_sigmoid",
        interpret=False, residency=sch.residency)
    _assert_kernel(fn, *_mbconv_args(one_chip, hw, c_in, c_mid, c_out, k))


def test_fusedmb_compiles(one_chip):
    """The first stride-2 Fused-MBConv block of EfficientNet-V2-S
    (24 -> 96 -> 48, 3 x 3 dense conv) at its 192 x 192 input."""
    sp = next(sp for sp in effnet_v2_block_specs(EffNetV2Config())
              if sp.family == "fusedmb" and sp.s == 2)
    hw = 192
    sch = get_fusedmb_schedule(BATCH, hw, hw, sp.c_in, sp.c_mid, sp.c_out,
                               sp.k, sp.s)
    fn = functools.partial(
        convdk_fusedmb_fused, stride=sp.s, tile_h=sch.tile_h, act="silu",
        interpret=False, residency=sch.residency)
    _assert_kernel(fn, _spec(one_chip, BATCH, hw, hw, sp.c_in),
                   _spec(one_chip, sp.k, sp.k, sp.c_in, sp.c_mid),
                   _spec(one_chip, sp.c_mid, sp.c_out))


def test_sharded_mbconv_compiles(mesh):
    """The sharded MBConv wrapper on a (2, 2) mesh of described chips:
    batch on "data", B0 block 3's 144 expanded channels on "model"."""
    c_in, c_out, e, k, s, hw = EFFICIENTNET_B0_MBCONV[3]
    c_mid = c_in * e
    sch = get_mbconv_schedule(BATCH, hw, hw, c_in, c_mid, c_out, k, s,
                              mesh_shape=(2, 2))
    rep = NamedSharding(mesh, PartitionSpec())

    def fn(*args):
        return convdk_mbconv_fused_sharded(
            *args, mesh=mesh, stride=s, tile_h=sch.tile_h, mode=sch.mode,
            interpret=False, residency=sch.residency,
            collective=sch.collective)

    hlo = jax.jit(fn).lower(
        *_mbconv_args(rep, hw, c_in, c_mid, c_out, k)).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo or "reduce-scatter" in hlo


def test_efficientnet_b0_apply_compiles(one_chip):
    """The whole served program: full-width B0 at 224, batch 8."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.mbconv import (
        EffNetConfig,
        efficientnet_b0_apply,
        efficientnet_b0_def,
    )
    from repro.models.param import abstract

    cfg = EffNetConfig()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract(efficientnet_b0_def(cfg)))
    kcfg = ConvKernelConfig(interpret=False)
    _assert_kernel(lambda p, x: efficientnet_b0_apply(p, x, cfg, kcfg),
                   params, _spec(one_chip, BATCH, 224, 224, 3))
