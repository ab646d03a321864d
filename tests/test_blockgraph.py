"""Cross-block pipelining battery.

Three layers, matching the feature's stack:

* **Model properties** — the per-pass splits (traffic, collective words,
  VMEM) sum EXACTLY to their whole-block counterparts over the full B0
  sweep, and the overlapped boundary latency is never above the
  serialized one (by hypothesis over arbitrary fitted coefficients).
* **Solver gate** — ``solve_network_schedule`` on the (2,4) b=8 B0 chain
  pipelines >= 1 boundary with modeled chain latency strictly below the
  serialized plan, and only annotates boundaries that are collective- and
  transition-free (the hazard preconditions).
* **Graph + executor** — ``models.blockgraph`` validates legal chains,
  rejects tampered overlap marks (streamed-set / WAW / WAR hazards), and
  the graph-lowered ``efficientnet_b0_apply`` is bit-exact — forward AND
  grad — with the explicit sequential loop it replaced, over mesh
  {(8,1),(2,4)} x {planned(pipelined), pinned retain, pinned recompute},
  under the same 8-virtual-device harness as ``test_distributed_fused``.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.autotune import (
    TPUConfig,
    greedy_network_schedule,
    mbconv_pass_vmem_bytes,
    mbconv_vmem_footprint_bytes,
    network_rows_from_table,
    solve_network_schedule,
)
from repro.core.perfmodel import (
    COLLECTIVE_MODES,
    RESIDENCY_MODES,
    MBConvShape,
    PerfCoefficients,
    boundary_overlap_us,
    mbconv_fused_traffic,
    mbconv_pass_traffic,
    mbconv_pass_us,
    sharded_mbconv_pass_costs,
    sharded_mbconv_traffic,
)
from repro.core.workloads import EFFICIENTNET_B0_MBCONV
from repro.models.blockgraph import (
    BlockGraph,
    BlockNode,
    GraphValidationError,
    StageIO,
    mbconv_stage_io,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HAVE_8 = jax.device_count() >= 8

B0_SHAPES = [
    MBConvShape(b=8, h=hw, w=hw, c_in=ci, c_mid=ci * e, c_out=co, k=k, s=s)
    for ci, co, e, k, s, hw in EFFICIENTNET_B0_MBCONV
]


# ---------------------------------------------------------------------------
# pass-split exactness: the halves always sum to the whole
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["retain", "recompute"])
@pytest.mark.parametrize("residency", RESIDENCY_MODES)
def test_pass_traffic_sums_to_whole(mode, residency):
    for shape in B0_SHAPES:
        for tile_h in (1, 4, 16):
            whole = mbconv_fused_traffic(shape, tile_h, mode,
                                         residency=residency)
            p1, p2 = mbconv_pass_traffic(shape, tile_h, mode,
                                         residency=residency)
            assert p1.read_words + p2.read_words == whole.read_words
            assert p1.write_words + p2.write_words == whole.write_words
            assert p1.dma_issues + p2.dma_issues == whole.dma_issues
            assert p1.dtype_bytes == p2.dtype_bytes == whole.dtype_bytes


@pytest.mark.parametrize("collective", COLLECTIVE_MODES)
@pytest.mark.parametrize("in_layout", ["replicated", "model_sharded"])
def test_sharded_pass_costs_sum_to_sharded_traffic(collective, in_layout):
    """Device bytes AND collective words of the pass split reconcile with
    ``sharded_mbconv_traffic`` (entry transition words included)."""
    for shape in B0_SHAPES:
        st_ = sharded_mbconv_traffic(
            shape, 4, "retain", (2, 4), collective=collective,
            in_layout=in_layout)
        pc = sharded_mbconv_pass_costs(
            shape, 4, "retain", (2, 4), collective=collective,
            in_layout=in_layout)
        dev = pc.pass1.total_bytes + pc.pass2.total_bytes
        assert dev == st_.device.total_bytes
        coll = pc.pass1_collective_words + pc.pass2_collective_words
        assert coll == st_.collective_words + st_.transition_words


@pytest.mark.parametrize("mode", ["retain", "recompute"])
@pytest.mark.parametrize("residency", RESIDENCY_MODES)
def test_pass_vmem_sums_to_footprint(mode, residency):
    tpu = TPUConfig()
    for shape in B0_SHAPES:
        for tile_h in (1, 4, 16):
            whole = mbconv_vmem_footprint_bytes(shape, tile_h, tpu,
                                                residency, mode)
            p1, p2 = mbconv_pass_vmem_bytes(shape, tile_h, tpu,
                                            residency, mode)
            assert p1 + p2 == whole
            assert p1 > 0 and p2 > 0


# ---------------------------------------------------------------------------
# overlap latency: pipelined <= serialized, for ANY fitted coefficients
# ---------------------------------------------------------------------------

_B0_CHAIN_COSTS = [
    sharded_mbconv_pass_costs(shape, 4, "retain", (2, 4))
    for shape in B0_SHAPES
]


@given(base=st.floats(-5000, 5000),
       per_mb=st.floats(0, 20000),
       per_issue=st.floats(0, 1000),
       per_coll_mb=st.floats(0, 20000))
@settings(max_examples=50, deadline=None)
def test_pipelined_never_above_serialized(base, per_mb, per_issue,
                                          per_coll_mb):
    """For every B0 boundary and any coefficient fit, the overlapped
    boundary latency max(p2, p1) sits at or below the serialized sum —
    the structural guarantee the CI gate's strictness rides on."""
    coeffs = PerfCoefficients(base_us=base, us_per_mb=per_mb,
                              us_per_dma_issue=per_issue,
                              us_per_collective_mb=per_coll_mb,
                              n_samples=1, rms_us=0.0)
    for prev, cur in zip(_B0_CHAIN_COSTS, _B0_CHAIN_COSTS[1:]):
        p2 = mbconv_pass_us(coeffs, prev.pass2,
                            prev.pass2_collective_words)
        p1 = mbconv_pass_us(coeffs, cur.pass1, cur.pass1_collective_words)
        serial = boundary_overlap_us(p2, p1, "serial")
        pipe = boundary_overlap_us(p2, p1, "pipelined")
        assert pipe == max(p2, p1)
        assert serial == p2 + p1
        assert pipe <= serial


# ---------------------------------------------------------------------------
# the solver gate
# ---------------------------------------------------------------------------

def test_network_dp_pipelines_b0_on_model_sharded_mesh():
    """The acceptance criterion: on (2,4) b=8, >= 1 boundary pipelines,
    modeled chain latency drops strictly below the serialized plan, and
    the annotation is byte-neutral + only marks hazard-free boundaries."""
    chain = network_rows_from_table(EFFICIENTNET_B0_MBCONV)
    plan = solve_network_schedule(chain, 8, (2, 4))
    assert len(plan.pipelined_boundaries) >= 1
    assert plan.pipelined_latency_us() < plan.serial_latency_us()
    # byte-neutral: the annotated plan still beats greedy (the PR-6 gate)
    greedy = greedy_network_schedule(chain, 8, (2, 4))
    assert plan.total_bytes < greedy.total_bytes
    for i in plan.pipelined_boundaries:
        bp = plan.blocks[i]
        assert bp.schedule.overlap == "pipelined"
        assert bp.entry_overlap == "pipelined"
        # hazard preconditions: no boundary regather, no entry repay
        assert bp.boundary_words == 0
        assert bp.schedule.transition_bytes == 0
    for bp in plan.blocks:
        if bp.entry_overlap == "serial":
            assert bp.schedule.overlap == "serial"
    # per-boundary report rows agree with the chain totals
    rows = plan.boundary_latencies()
    saving = sum(r["serialized_us"] - r["overlap_us"] for r in rows)
    assert plan.serial_latency_us() - plan.pipelined_latency_us() \
        == pytest.approx(saving)


def test_network_dp_degenerate_mesh_still_sound():
    """(1,1) b=1: whatever the annotation finds, pipelined <= serialized
    and every accessor stays self-consistent."""
    chain = network_rows_from_table(EFFICIENTNET_B0_MBCONV)
    plan = solve_network_schedule(chain, 1, (1, 1))
    assert plan.pipelined_latency_us() <= plan.serial_latency_us()
    assert len(plan.boundary_latencies()) == len(plan.blocks) - 1


# ---------------------------------------------------------------------------
# graph validation: legal chains pass, tampered overlap marks raise
# ---------------------------------------------------------------------------

def _chain(n=3, mode="retain", pipelined=()):
    nodes = []
    for i in range(n):
        p1, p2 = mbconv_stage_io(i, mode=mode, residual=False)
        nodes.append(BlockNode(
            index=i, name=f"mbconv{i}", pass1=p1, pass2=p2,
            entry_overlap="pipelined" if i in pipelined else "serial"))
    return nodes


def test_graph_validates_legal_pipelined_chain():
    for mode in ("retain", "recompute"):
        g = BlockGraph(nodes=tuple(_chain(4, mode, pipelined=(1, 2, 3))))
        g.validate()
        assert g.pipelined_boundaries == (1, 2, 3)


def test_graph_rejects_first_node_pipelined():
    with pytest.raises(GraphValidationError, match="no producer"):
        BlockGraph(nodes=tuple(_chain(2, pipelined=(0,)))).validate()


def test_graph_rejects_misindexed_chain():
    nodes = _chain(3)
    nodes[1] = BlockNode(index=2, name="mbconv2", pass1=nodes[1].pass1,
                         pass2=nodes[1].pass2)
    with pytest.raises(GraphValidationError, match="chain order"):
        BlockGraph(nodes=tuple(nodes)).validate()


def test_graph_rejects_non_activation_stream():
    """A side buffer flowing producer-pass-2 -> consumer-pass-1 makes the
    boundary unpipelinable — the validator must catch the tamper."""
    nodes = _chain(2, pipelined=(1,))
    tampered = StageIO.of(nodes[1].pass1.reads | {"dw0"},
                          nodes[1].pass1.writes)
    nodes[1] = BlockNode(index=1, name="mbconv1", pass1=tampered,
                         pass2=nodes[1].pass2, entry_overlap="pipelined")
    nodes[0] = BlockNode(index=0, name="mbconv0", pass1=nodes[0].pass1,
                         pass2=StageIO.of(nodes[0].pass2.reads,
                                          nodes[0].pass2.writes | {"dw0"}))
    with pytest.raises(GraphValidationError, match="boundary activation"):
        BlockGraph(nodes=tuple(nodes)).validate()


def test_graph_rejects_write_write_hazard():
    nodes = _chain(2, pipelined=(1,))
    tampered = StageIO.of(nodes[1].pass1.reads,
                          nodes[1].pass1.writes | {"act1"})
    nodes[1] = BlockNode(index=1, name="mbconv1", pass1=tampered,
                         pass2=nodes[1].pass2, entry_overlap="pipelined")
    with pytest.raises(GraphValidationError, match="write-write"):
        BlockGraph(nodes=tuple(nodes)).validate()


def test_graph_rejects_write_after_read_hazard():
    """A recompute producer still reads ITS entry activation in pass 2;
    a consumer pass 1 clobbering it must be rejected."""
    nodes = _chain(2, mode="recompute", pipelined=(1,))
    tampered = StageIO.of(nodes[1].pass1.reads,
                          nodes[1].pass1.writes | {"act0"})
    nodes[1] = BlockNode(index=1, name="mbconv1", pass1=tampered,
                         pass2=nodes[1].pass2, entry_overlap="pipelined")
    with pytest.raises(GraphValidationError, match="still reads"):
        BlockGraph(nodes=tuple(nodes)).validate()


def test_graph_rejects_bad_overlap_mode():
    with pytest.raises(ValueError):
        _p1, _p2 = mbconv_stage_io(0)
        BlockNode(index=0, name="mbconv0", pass1=_p1, pass2=_p2,
                  entry_overlap="overlapped")


def test_build_graph_matches_plan_annotation():
    """The built graph inherits the plan's solved overlap marks 1:1 and
    validates — the lowering path CI exercises, minus the jit."""
    from repro.configs.efficientnet_b0 import efficientnet_b0_smoke
    from repro.models.blockgraph import build_block_graph
    from repro.models.mbconv import (
        block_chain_rows, effnet_block_specs, efficientnet_b0_def,
    )
    from repro.models.param import materialize
    from repro.core.autotune import get_network_plan
    cfg = efficientnet_b0_smoke(width_mult=0.125, num_classes=4)
    params = materialize(efficientnet_b0_def(cfg), jax.random.key(0))
    specs = effnet_block_specs(cfg)
    plan = get_network_plan(block_chain_rows(specs, 16, 16), 8, (2, 4),
                            dtype_bytes=4, se_ratio=cfg.se_ratio)
    g = build_block_graph(specs, params, plan=plan)
    g.validate()
    assert g.pipelined_boundaries == plan.pipelined_boundaries
    for node, bp in zip(g.nodes, plan.blocks):
        assert node.entry_overlap == bp.entry_overlap
    # serial build: same chain, no overlap marks
    g0 = build_block_graph(specs, params)
    g0.validate()
    assert g0.pipelined_boundaries == ()


# ---------------------------------------------------------------------------
# executor parity: graph lowering == sequential loop, fwd AND grad
# ---------------------------------------------------------------------------

_PREAMBLE = textwrap.dedent("""
    import os
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat import make_mesh
    from repro.configs.base import ConvKernelConfig, SchedulePin
    from repro.models.mbconv import (
        EffNetConfig, block_chain_rows, efficientnet_b0_apply,
        efficientnet_b0_def, effnet_block_specs, mbconv_block,
    )
    from repro.models.param import materialize

    assert jax.device_count() >= 8, jax.devices()

    cfg = EffNetConfig(width_mult=0.125, num_classes=4)
    params = materialize(efficientnet_b0_def(cfg), jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (8, 24, 24, 3),
                             jnp.float32)
    specs = effnet_block_specs(cfg)

    def parse_mesh(text):
        dp, mp = (int(t) for t in text.split("x"))
        return make_mesh((dp, mp), ("data", "model"))

    def loop_reference(params, imgs, kcfg, mesh, plan):
        '''The pre-graph executor: stem + explicit sequential block loop
        + head, threading the plan pins exactly as the old code did, the
        chain in the kernels' lane layout (zero stem filters past the
        stem's channels, zero head rows past the last block's).'''
        from repro.kernels.common import to_lanes
        dt = jnp.dtype(cfg.dtype)
        x = jax.lax.conv_general_dilated(
            imgs.astype(dt), to_lanes(params["stem"].astype(dt)), (2, 2),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.silu(x)
        if plan is not None and mesh is not None \\
                and plan.stem_layout == "model_sharded":
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P
            from repro.kernels.convdk_sharded import MODEL_AXIS, _batch_axes
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, _P(_batch_axes(mesh), None, None,
                                          MODEL_AXIS)))
        for i, sp in enumerate(specs):
            if plan is not None:
                bp = plan.blocks[i]
                pin = SchedulePin(mode=bp.schedule.mode,
                                  residency=bp.schedule.residency,
                                  collective=bp.schedule.collective)
                x, _ = mbconv_block(x, params[f"block{i}"], stride=sp.s,
                                    cfg=kcfg, mesh=mesh, pin=pin,
                                    in_layout=bp.in_layout,
                                    overlap=bp.entry_overlap)
            else:
                x, _ = mbconv_block(x, params[f"block{i}"], stride=sp.s,
                                    cfg=kcfg, mesh=mesh)
        w_head = params["head"].astype(x.dtype)
        w_head = jnp.pad(w_head, ((0, x.shape[-1] - w_head.shape[0]),
                                  (0, 0)))
        x = jax.nn.silu(jnp.einsum("bhwc,cd->bhwd", x, w_head))
        x = x.mean(axis=(1, 2))
        return x @ params["cls_w"].astype(x.dtype) \\
            + params["cls_b"].astype(x.dtype)

    def assert_bitexact_fwd_and_grad(kcfg, mesh, plan, tag):
        got = efficientnet_b0_apply(params, imgs, cfg, kcfg=kcfg,
                                    mesh=mesh, plan=plan)
        want = loop_reference(params, imgs, kcfg, mesh, plan)
        assert jnp.array_equal(got, want), f"{tag}: forward diverged"
        g_got = jax.grad(lambda p: efficientnet_b0_apply(
            p, imgs, cfg, kcfg=kcfg, mesh=mesh, plan=plan).sum())(params)
        g_want = jax.grad(lambda p: loop_reference(
            p, imgs, kcfg, mesh, plan).sum())(params)
        leaves_got, tdef_got = jax.tree_util.tree_flatten(g_got)
        leaves_want, tdef_want = jax.tree_util.tree_flatten(g_want)
        assert tdef_got == tdef_want, tag
        for a, b in zip(leaves_got, leaves_want):
            assert jnp.array_equal(a, b), f"{tag}: grad diverged"
""")


def run_case(body: str) -> None:
    src = _PREAMBLE + textwrap.dedent(body)
    if HAVE_8:
        exec(compile(src, "<blockgraph-parity-case>", "exec"),
             {"__name__": "__blockgraph_parity__"})
        return
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.setdefault("CONVDK_RESIDUAL_BARRIER", "on")
    res = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]


@pytest.mark.parametrize("mesh", ["8x1", "2x4"])
def test_planned_pipelined_chain_parity(mesh):
    """The tentpole parity: the graph-lowered apply under the SOLVED plan
    (pipelined boundaries included) is bit-exact — forward and grad —
    with the explicit sequential loop threading the same plan."""
    run_case(f"""
    mesh = parse_mesh("{mesh}")
    kcfg = ConvKernelConfig(interpret=True)
    from repro.core.autotune import get_network_plan
    from repro.core.perfmodel import PerfCoefficients, set_perf_coefficients
    from repro.kernels import conv_mesh_shape
    # the default fit's base term floors the smoke model's tiny passes to
    # zero latency, so the annotation (rightly) finds no win; install a
    # positive fit so the solved plan REALLY pipelines for the parity run
    set_perf_coefficients(PerfCoefficients(
        base_us=0.0, us_per_mb=1000.0, us_per_dma_issue=1.0,
        us_per_collective_mb=1000.0, n_samples=1, rms_us=0.0))
    try:
        plan = get_network_plan(block_chain_rows(specs, 12, 12), 8,
                                conv_mesh_shape(mesh), dtype_bytes=4,
                                se_ratio=cfg.se_ratio)
        if conv_mesh_shape(mesh)[1] > 1:
            assert len(plan.pipelined_boundaries) >= 1, plan
        assert_bitexact_fwd_and_grad(kcfg, mesh, plan, "planned/{mesh}")
    finally:
        set_perf_coefficients(None)
    print("PLANNED_PARITY_OK {mesh}")
    """)


@pytest.mark.parametrize("mesh", ["8x1", "2x4"])
@pytest.mark.parametrize("mode", ["retain", "recompute"])
def test_pinned_mode_chain_parity(mesh, mode):
    """Graph vs loop under a pinned pass-2 mode (autotune off, so the pin
    reaches every block unchanged) — fwd and grad bit-exact."""
    run_case(f"""
    mesh = parse_mesh("{mesh}")
    kcfg = ConvKernelConfig(interpret=True, autotune=False,
                            mbconv_mode="{mode}")
    assert_bitexact_fwd_and_grad(kcfg, mesh, None, "{mode}/{mesh}")
    print("PINNED_PARITY_OK {mode} {mesh}")
    """)
