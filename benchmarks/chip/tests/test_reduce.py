"""The trace reduction, on intervals made up here and on a small trace
recorded on a TPU v5e (MobileNet-V3-Large, batch 8 at 224, three steps of
one jitted apply, with the host spans ``window``, ``dispatch`` and
``block_until_ready``)."""

from pathlib import Path

import pytest

import reduce
from reduce import Op, Step, Trace

FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" \
    / "v3l_b8_3steps.xplane.pb.gz"


def test_union_and_covered():
    merged = reduce.union([(5, 8), (0, 2), (1, 3), (8, 9), (20, 20)])
    assert merged == [(0, 3), (5, 9)]
    assert reduce.covered(merged, 2, 6) == 2
    assert reduce.covered(merged, 10, 30) == 0


def made_up():
    # one device: a step [0, 100) with a Pallas kernel [10, 40), glue
    # [40, 50), a second kernel [50, 80); a gap, a second step [200, 260)
    # with one kernel [210, 250); the window [0, 300)
    ops = [Op(0, 10, "stem", False), Op(10, 40, "k1", True),
           Op(40, 50, "pad", False), Op(50, 80, "k2", True),
           Op(80, 100, "head", False), Op(210, 250, "k1", True)]
    steps = [Step(0, 100, "jit_timed_step(1)"), Step(200, 260,
                                                     "jit_timed_step(1)")]
    host = [(0, 300, "window"), (100, 190, "dispatch")]
    return Trace(ops=[ops], steps=[steps], host_spans=host, window=(0, 300))


def test_busy_idle_and_chain_on_made_up_intervals():
    t = made_up()
    assert reduce.busy_s(t) == pytest.approx(140e-9)
    assert reduce.idle_share(t) == pytest.approx(1 - 140 / 300)
    assert reduce.step_device_s(t, ("jit_timed_step",)) == \
        pytest.approx(160e-9)
    assert reduce.step_device_s(t, ("jit_other",)) == 0
    ct = reduce.chain_time(t, ("jit_timed_step",))
    # chains [10, 80) and [210, 250): stem and head lie outside them
    assert ct.steps == 2
    assert ct.busy_s == pytest.approx(110e-9)
    assert ct.pallas_s == pytest.approx(100e-9)
    assert ct.other_s == pytest.approx(10e-9)
    gaps = reduce.idle_gaps(t)
    assert gaps[0] == ["dispatch", pytest.approx(110e-9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert reduce.top_ops(t, 1) == [["k1", pytest.approx(70e-9)]]


def test_op_names_and_pallas_from_hlo_text():
    hlo = ('%convdk_mbconv_fused.16 = f32[8,56,56,24]{3,2,1,0} custom-call('
           'f32[8,113,120,128]{3,2,1,0} %pad.107), '
           'custom_call_target="tpu_custom_call"')
    assert reduce.is_pallas(hlo)
    assert reduce.op_name(hlo) == \
        "convdk_mbconv_fused.16 f32[8,56,56,24]{3,2,1,0}"
    assert not reduce.is_pallas("%pad.1 = f32[8]{0} pad(f32[4]{0} %x)")


@pytest.fixture(scope="module")
def chip_trace():
    return reduce.load(FIXTURE)


def test_recorded_trace_has_the_steps_and_kernels(chip_trace):
    t = chip_trace
    assert len(t.ops) == 1 and len(t.steps) == 1
    steps = reduce.timed_steps(t, ("jit__lambda",))[0]
    assert len(steps) == 3
    # the V3-L chain launches 23 Pallas kernels per step at batch 8
    assert sum(o.pallas for o in t.ops[0]) == 3 * 23
    assert all(o.name.startswith("convdk_mbconv_fused")
               for o in t.ops[0] if o.pallas)


def test_recorded_trace_reduces_to_sane_shares(chip_trace):
    t = chip_trace
    assert 0.006 < t.window_s < 0.007
    busy = reduce.busy_s(t)
    step = reduce.step_device_s(t, ("jit__lambda",))
    assert 0 < busy <= t.window_s
    # the XLA Ops line runs one operation at a time: busy is the steps
    assert busy == pytest.approx(step, rel=1e-3)
    ct = reduce.chain_time(t, ("jit__lambda",))
    assert ct.steps == 3
    assert ct.busy_s < step
    assert ct.pallas_s + ct.other_s == pytest.approx(ct.busy_s, rel=1e-3)
    assert 0.1 < ct.other_s / ct.busy_s < 0.3
    share = reduce.idle_share(t)
    assert 0.3 < share < 0.6
    gaps = reduce.idle_gaps(t)
    assert gaps and gaps[0][0] == "block_until_ready"
    top = reduce.top_ops(t)
    assert len(top) == 10 and top[0][0].startswith("convdk_mbconv_fused")
