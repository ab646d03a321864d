"""Compile rehearsal: the ConvDK kernels compiled for a described TPU v5e.

The TPU compiler ships with the installed jaxlib and compiles for a chip
that is described, not attached, so these tests show for free what
interpret mode hides: tile alignment of blocks and DMA windows, strided
loads, mask shapes and the scoped-VMEM limit.  Every case compiles one
kernel (or a whole network's named entry) at published widths with
``interpret=False`` and asserts that the Mosaic kernel is in the program
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import contextlib
import functools
import re

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
from repro.models import mbconv as M
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


def _mbconv_args(sharding, hw, c_in, c_mid, c_out, k, se=True,
                 expand=True, batch=BATCH):
    """Abstract kernel arguments; ``expand=False`` passes ``w_exp=None``
    (a block with no expand convolution)."""
    c_se = max(1, c_in // 4)
    se_args = ((_spec(sharding, c_mid, c_se), _spec(sharding, c_se),
                _spec(sharding, c_se, c_mid), _spec(sharding, c_mid))
               if se else (None,) * 4)
    return (_spec(sharding, batch, hw, hw, c_in),
            _spec(sharding, c_in, c_mid) if expand else None,
            _spec(sharding, k, k, c_mid),
            *se_args, _spec(sharding, c_mid, c_out))


def _kernel_operands(hlo: str) -> dict:
    """{kernel name: operand count} of each Pallas launch in compiled HLO
    text (the instruction name less its ``.<n>`` suffix)."""
    found = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"%([A-Za-z_0-9]+?)(?:\.\d+)? = .*?custom-call\(([^)]*)\)",
                      line)
        assert m is not None, line
        found[m.group(1)] = m.group(2).count("%")
    return found


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
    """B0 block 0 (112 x 112, no expand) and block 3 (144 channels,
    5 x 5, stride 2), each pass-2 mode under its own solved schedule."""
    c_in, c_out, e, k, s, hw = EFFICIENTNET_B0_MBCONV[block]
    c_mid = c_in * e
    sch = get_mbconv_schedule(BATCH, hw, hw, c_in, c_mid, c_out, k, s,
                              mode=mode)
    fn = functools.partial(
        convdk_mbconv_fused, stride=s, tile_h=sch.tile_h, mode=mode,
        exp_act="silu" if e > 1 else None, interpret=False,
        residency=sch.residency)
    _assert_kernel(fn, *_mbconv_args(one_chip, hw, c_in, c_mid, c_out, k,
                                     expand=e > 1))


@pytest.mark.parametrize("model", ["b0", "v3l"])
def test_identity_expand_drops_the_expand_operand(one_chip, model):
    """Block 0 of B0 (32 -> 32 -> 16, SE) and of V3-L (16 -> 16 -> 16, no
    SE) at the offline cells' batch 256 and 112 x 112, under the solved
    schedule: with ``w_exp=None`` every launch that recomputes the DW
    strip takes one operand fewer than with an explicit square ``w_exp``
    (the expand weight and its matmul are gone from the kernel)."""
    batch, hw = 256, 112
    if model == "b0":
        c_in, c_out, _, k, s, _ = EFFICIENTNET_B0_MBCONV[0]
        se, act, se_ratio = True, "silu", 0.25
        se_act, gate_act = "silu", "sigmoid"
    else:
        c_mid, c_out, k, s, se, act = MOBILENET_V3_LARGE_BLOCKS[0]
        c_in, se_ratio, se_act, gate_act = c_mid, 0.0, "relu", \
            "hard_sigmoid"
    sch = get_mbconv_schedule(batch, hw, hw, c_in, c_in, c_out, k, s,
                              se_ratio=se_ratio, act=act)
    assert sch.mode == "recompute", sch
    fn = functools.partial(
        convdk_mbconv_fused, stride=s, tile_h=sch.tile_h, mode=sch.mode,
        exp_act=None, dw_act=act, se_act=se_act, gate_act=gate_act,
        interpret=False, residency=sch.residency)
    ops = {}
    for expand in (True, False):
        args = _mbconv_args(one_chip, hw, c_in, c_in, c_out, k, se=se,
                            expand=expand, batch=batch)
        ops[expand] = _kernel_operands(
            jax.jit(fn).lower(*args).compile().as_text())
    launches = ({"mbconv_pass1", "mbconv_pass2_recompute"} if se
                else {"mbconv_pass2_recompute"})
    assert set(ops[False]) == set(ops[True]) == launches, ops
    for name in launches:
        assert ops[False][name] == ops[True][name] - 1, (name, ops)


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


def _v2s_fused_launch(sharding, block, project=None):
    """(kernel fn, abstract args) of V2-S block ``block`` at the offline
    cell's batch 64 and its own input side (the stem halves 384 to 192,
    blocks 2 and 6 halve it again), under the solved schedule.
    ``project`` overrides the spec: True passes a (square, for block 0)
    ``w_proj``, False passes None."""
    sp = effnet_v2_block_specs(EffNetV2Config())[block]
    batch, hw = 64, 192 if block <= 2 else 96 if block <= 6 else 48
    sch = get_fusedmb_schedule(batch, hw, hw, sp.c_in, sp.c_mid, sp.c_out,
                               sp.k, sp.s)
    fn = functools.partial(
        convdk_fusedmb_fused, stride=sp.s, tile_h=sch.tile_h, act="silu",
        interpret=False, residency=sch.residency)
    if project is None:
        project = sp.expand_ratio != 1
    return fn, (_spec(sharding, batch, hw, hw, sp.c_in),
                _spec(sharding, sp.k, sp.k, sp.c_in, sp.c_mid),
                _spec(sharding, sp.c_mid, sp.c_out) if project else None)


@pytest.mark.parametrize("block", [0, 2, 3, 6, 7], ids=[
    "block0_e1", "block2_s2", "block3_c48", "block6_s2_c48", "block7_c64"])
def test_v2s_fused_blocks_compile_at_the_cell_size(one_chip, block):
    """V2-S fused blocks at batch 64 and their own input sides, at the
    cell's "highest" matmul precision, where a float32 contraction's
    operands split into bfloat16 parts in VMEM (block 2's solved tile_h
    16 once needed 20 MiB of the 16 MiB limit there): block 0 (expansion
    1: 24 -> 24, no projection, 192 x 192), block 2 (the first stride 2:
    24 -> 96 -> 48), block 3 (48 -> 192 -> 48 at 96 x 96), block 6 (48 ->
    192 -> 64, stride 2) and block 7 (64 -> 256 -> 64 at 48 x 48).  Every
    one packs taps: 5 per contraction at c_in 24, 2 at 48 and 64."""
    fn, args = _v2s_fused_launch(one_chip, block)
    assert (args[2] is None) == (block == 0)
    with jax.default_matmul_precision("highest"):
        _assert_kernel(fn, *args)


def test_no_projection_launch_drops_the_projection_operand(one_chip):
    """V2-S block 0 at the cell's size: with ``w_proj=None`` the launch
    takes one operand fewer than with an explicit square ``w_proj``."""
    ops = {}
    for project in (True, False):
        fn, args = _v2s_fused_launch(one_chip, 0, project)
        ops[project] = _kernel_operands(
            jax.jit(fn).lower(*args).compile().as_text())
    assert set(ops[False]) == set(ops[True]) == {"fusedmb"}, ops
    assert ops[False]["fusedmb"] == ops[True]["fusedmb"] - 1, ops


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


# each family's named entry at (batch, resolution, matmul precision):
# V2-S as its benchmark cell runs it, B0 and V3-L at 224, batch 8
_NAMED_ENTRIES = {
    "efficientnet_b0": (M.EffNetConfig, M.efficientnet_b0_def,
                        M.efficientnet_b0_apply, BATCH, 224, None),
    "mobilenet_v3_large": (M.MobileNetV3Config, M.mobilenet_v3_def,
                           M.mobilenet_v3_apply, BATCH, 224, None),
    "efficientnet_v2_s": (M.EffNetV2Config, M.efficientnet_v2_s_def,
                          M.efficientnet_v2_s_apply, 64, 384, "highest"),
}


@functools.lru_cache(maxsize=None)
def _named_entry_hlo(model, one_chip) -> str:
    """Compiled HLO text of ``model``'s named entry (``_NAMED_ENTRIES``),
    compiled once per worker for the tests that read it."""
    from repro.configs.base import ConvKernelConfig
    from repro.models.param import abstract

    config, define, apply, batch, res, precision = _NAMED_ENTRIES[model]
    cfg = config()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract(define(cfg)))
    kcfg = ConvKernelConfig(interpret=False)
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        return jax.jit(lambda p, x: apply(p, x, cfg, kcfg)).lower(
            params, _spec(one_chip, batch, res, res, 3)).compile().as_text()


@pytest.mark.parametrize("model", sorted(_NAMED_ENTRIES))
def test_named_entry_compiles(one_chip, model):
    """The whole served program of each family at published widths,
    through its named entry."""
    assert "tpu_custom_call" in _named_entry_hlo(model, one_chip)


def _entry_instructions(hlo: str) -> dict:
    """{name: (opcode, shape, line)} of the compiled program's entry
    computation."""
    body = re.search(r"\nENTRY [^\n]*\{\n(.*?)\n\}", hlo, re.S).group(1)
    found = {}
    for line in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w\-]+)\(", line)
        if m:
            found[m.group(1)] = (m.group(3), m.group(2), line)
    return found


@pytest.mark.parametrize("model", sorted(_NAMED_ENTRIES))
def test_named_entry_stages_without_a_copy(one_chip, model):
    """No standalone ``pad`` or ``copy`` of an activation feeds a Pallas
    kernel of a block at 56 x 56 or above: each kernel stages the block's
    activation as the previous block (or the stem) wrote it, in the lane
    layout, and builds its conv halo in VMEM.  The stem writes that layout
    with no ``pad`` or ``copy`` of its own."""
    ops = _entry_instructions(_named_entry_hlo(model, one_chip))
    kernels = 0
    for name, (opcode, _shape, line) in ops.items():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        kernels += 1
        args = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        for arg in re.findall(r"%(\S+?)(?:,|$)", args):
            op, shape, _ = ops.get(arg, ("parameter", "", ""))
            dims = re.match(r"\w+\[([\d,]*)\]", shape)
            dims = [int(d) for d in dims.group(1).split(",")] if (
                dims and dims.group(1)) else []
            assert not (op in ("pad", "copy") and len(dims) == 4
                        and dims[1] >= 56), (name, arg, op, shape)
    assert kernels > 0
    stem = [(name, op) for name, (op, _, line) in ops.items()
            if "/stem/" in line and op in ("pad", "copy")]
    assert not stem, stem
