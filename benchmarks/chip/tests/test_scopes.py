"""The join of trace ops with the compiled program's scopes, on a
hand-written HLO text and made-up ops, and the readers built on it."""

import types
from pathlib import Path

import pytest

import run
import scopes
from reduce import Op, Step, Trace

METRICS = Path(__file__).resolve().parents[1] / "metrics"

HLO = r'''
%fused_computation.1 (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  ROOT %neg.1 = f32[8,16]{1,0} negate(%param_0), metadata={op_name="jit(timed_step)/mbconv0/neg"}
}

ENTRY %main.9 (p__block0____dw__.1: f32[3,3,16], x.1: f32[8,16]) -> f32[8,4] {
  %x.1 = f32[8,16]{1,0} parameter(1), metadata={op_name="x"}
  %p__block0____dw__.1 = f32[3,3,16]{2,1,0} parameter(0), metadata={op_name="p[\'block0\'][\'dw\']"}
  %copy.3 = f32[8,16]{0,1} copy(%x.1), metadata={op_name="x"}
  %copy-start.1 = (f32[3,3,16], f32[3,3,16], u32[]) copy-start(%p__block0____dw__.1)
  %copy-done.1 = f32[3,3,16]{2,1,0} copy-done(%copy-start.1)
  %stem_fusion = f32[8,16]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(timed_step)/stem/logistic" source_file="m.py"}
  %pad.102 = f32[8,128]{1,0} pad(%stem_fusion, %c), padding=0_0x0_112, metadata={op_name="jit(timed_step)/mbconv0/jit(convdk_mbconv_fused)/jit(_pad)/pad"}
  %mbconv_pass1.3 = f32[8,128]{1,0} custom-call(%pad.102, %copy-done.1), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "TUxJUlx"}}, metadata={op_name="jit(timed_step)/mbconv0/jit(convdk_mbconv_fused)/mbconv_pass1/pallas_call"}
  %bitcast.7 = f32[8,128]{1,0} bitcast(%mbconv_pass1.3)
  %add.4 = f32[8,16]{1,0} add(%bitcast.7, %stem_fusion), metadata={op_name="jit(timed_step)/mbconv0/add"}
  %fusedmb_kernel.1 = f32[8,16]{1,0} custom-call(%add.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(timed_step)/fusedmb1/jit(convdk_fusedmb_fused)/fusedmb/pallas_call"}
  %p__head__.1 = f32[16,4]{1,0} parameter(2), metadata={op_name="p[\'head\']"}
  %copy.9 = f32[16,4]{0,1} copy(%p__head__.1), metadata={op_name="p[\'head\']"}
  ROOT %dot.5 = f32[8,4]{1,0} dot(%fusedmb_kernel.1, %copy.9), metadata={op_name="jit(timed_step)/head/dot_general"}
  %constant.1 = f32[] constant(0)
}
'''


def test_op_scopes_from_hlo_text():
    m = scopes.op_scopes(HLO)
    assert m["stem_fusion"] == ("stem", "XLA")
    assert m["pad.102"] == ("mbconv0", "XLA")
    assert m["mbconv_pass1.3"] == ("mbconv0", "mbconv_pass1")
    assert m["add.4"] == ("mbconv0", "XLA")
    assert m["fusedmb_kernel.1"] == ("fusedmb1", "fusedmb")
    assert m["dot.5"] == ("head", "XLA")
    assert m["neg.1"] == ("mbconv0", "XLA")
    # arguments go to the scope that reads them
    assert m["copy.3"] == ("stem", "XLA")              # the images
    assert m["p__block0____dw__.1"] == ("mbconv0", "XLA")
    assert m["copy.9"] == ("head", "XLA")
    # no metadata: the first operand's scope
    assert m["copy-start.1"] == ("mbconv0", "XLA")
    assert m["copy-done.1"] == ("mbconv0", "XLA")
    assert m["bitcast.7"] == ("mbconv0", "XLA")
    assert "constant.1" not in m
    # a program that names no scope maps nothing
    plain = HLO.replace("/stem/", "/").replace("/mbconv0/", "/") \
        .replace("/fusedmb1/", "/").replace("/head/", "/")
    assert scopes.op_scopes(plain) == {}


def made_up():
    # two steps [0, 100) and [200, 300); one op is not in the map and a
    # gap [90, 100) inside the first step is idle
    ops = [Op(0, 10, "copy.3 f32[8,16]", False),
           Op(10, 20, "stem_fusion f32[8,16]", False),
           Op(20, 30, "pad.102 f32[8,128]", False),
           Op(30, 60, "mbconv_pass1.3 f32[8,128]", True),
           Op(60, 70, "add.4 f32[8,16]", False),
           Op(70, 80, "dot.5 f32[8,4]", False),
           Op(80, 90, "mystery.1 f32[1]", False),
           Op(200, 240, "mbconv_pass1.3 f32[8,128]", True),
           Op(240, 300, "dot.5 f32[8,4]", False)]
    steps = [Step(0, 100, "jit_timed_step(1)"),
             Step(200, 300, "jit_timed_step(1)")]
    return Trace(ops=[ops], steps=[steps], host_spans=[(0, 400, "window")],
                 window=(0, 400))


def test_scope_time_and_closure():
    st = scopes.scope_time(made_up(), ("jit_timed_step",),
                           scopes.op_scopes(HLO))
    assert st.steps == 2
    assert st.step_s == pytest.approx(200e-9)
    assert st.pairs[("mbconv0", "mbconv_pass1")] == pytest.approx(70e-9)
    assert st.pairs[("mbconv0", "XLA")] == pytest.approx(20e-9)
    assert st.pairs[("stem", "XLA")] == pytest.approx(20e-9)
    assert st.pairs[("head", "XLA")] == pytest.approx(70e-9)
    assert st.unattributed_s == pytest.approx(10e-9)
    c = scopes.closure(st)
    assert c == pytest.approx({"pallas": 35.0, "block_glue": 10.0,
                               "stem_head": 45.0, "unattributed": 5.0})
    assert scopes.top_pairs(st, 2) == [
        ["mbconv0 / mbconv_pass1", pytest.approx(70e-9)],
        ["head / XLA", pytest.approx(70e-9)]]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reading(trace):
    window = run.Window(step_prefixes=("jit_timed_step",), batch=8)
    return types.SimpleNamespace(cell=None, window=window, trace=trace)


def test_readers(monkeypatch):
    from repro.core import telemetry
    texts = []
    monkeypatch.setattr(scopes, "timed_step_hlo",
                        lambda cell, batch: texts.append(batch) or HLO)
    telemetry.reset()
    with telemetry.span(scopes.PLAN_SPAN):
        pass
    reading = _reading(made_up())
    solve = _reader("plan_solve_s")(reading)
    assert solve == telemetry.snapshot()["spans"][scopes.PLAN_SPAN][
        "total_s"]
    assert _reader("block_glue_share.throughput")(reading) == \
        pytest.approx(10.0)
    # spans taken after the readers started do not count
    with telemetry.span(scopes.PLAN_SPAN):
        pass
    assert _reader("stem_head_share.throughput")(reading) == \
        pytest.approx(45.0)
    assert _reader("plan_solve_s")(reading) == solve
    assert texts == [8]                       # compiled once per run
    # no trace, no span, no scopes: nothing to read, nothing raised
    telemetry.reset()
    empty = _reading(None)
    assert _reader("block_glue_share.throughput")(empty) is None
    assert _reader("plan_solve_s")(empty) is None
    monkeypatch.setattr(scopes, "timed_step_hlo",
                        lambda cell, batch: "ENTRY %m () -> f32[] {}")
    assert _reader("stem_head_share.throughput")(_reading(made_up())) \
        is None


@pytest.mark.parametrize("name", ["b0.offline.r224.b256",
                                  "v3l.offline.r224.b256"])
def test_timed_step_hlo_names_stem_blocks_head(name):
    """The readers' own compile of the timed step (tiny, on the CPU)
    places the program's scopes."""
    import tiny
    import work
    cell = tiny.tiny_cell(name)
    m = scopes.op_scopes(scopes.timed_step_hlo(cell, cell.mix["batch"]))
    found = {s for s, _ in m.values()}
    assert {"stem", "head", "mbconv0"} <= found
    blocks = work.reference_module(cell.cfg).blocks(cell.cfg)
    assert sum(scopes.is_block(s) for s in found) == len(blocks)


FIXTURE = Path(__file__).resolve().parents[1] / "fixtures" \
    / "v3l_b8_3steps_scoped"


def test_recorded_v3l_steps_by_scope():
    """A trace recorded on a TPU v5e (MobileNet-V3-Large, batch 8 at 224,
    three steps of ``timed_step``; ``record_fixture.py``) with the HLO
    text of the executable that ran: every op of the steps is placed,
    the four-way split closes, and block 0 owns its input pad."""
    import gzip
    import reduce
    trace = reduce.load(f"{FIXTURE}.xplane.pb.gz")
    with gzip.open(f"{FIXTURE}.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    m = scopes.op_scopes(hlo)
    st = scopes.scope_time(trace, ("jit_timed_step",), m)
    assert st.steps == 3
    assert st.unattributed_s == 0
    assert all(scopes.instruction(o) in m for d in trace.ops for o in d)
    c = scopes.closure(st)
    assert sum(c.values()) == pytest.approx(100.0, abs=2.0)
    found = {sc for sc, _ in st.pairs}
    assert found == {"stem", "head"} | {f"mbconv{i}" for i in range(15)}
    # block 0's first kernel reads a pad that block 0 owns, and that pad
    # takes device time in every step
    first = next(line for line in hlo.splitlines()
                 if scopes.INSTRUCTION.match(line)
                 and m.get(scopes.INSTRUCTION.match(line).group(1),
                           ("", ""))[0] == "mbconv0"
                 and reduce.is_pallas(line))
    pad = scopes.OPERAND.findall(first, first.index(" = "))[0]
    assert pad.startswith("pad") and m[pad] == ("mbconv0", "XLA")
    runs = [o for o in trace.ops[0] if scopes.instruction(o) == pad]
    assert len(runs) == 3 and all(o.end > o.start for o in runs)
    assert st.pairs[("mbconv0", "mbconv_pass2_recompute")] > 0
