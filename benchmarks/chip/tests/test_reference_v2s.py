"""EfficientNet-V2-S in the benchmark: its plain reference against the
program's interpret-mode apply at a small width, its Fused-MBConv work
count against a sum written out from the paper's table, the two readers
of the fused kernel on made-up traces, and a whole run of its cell cut
down."""

import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import run
import scopes
import weights
import work
import work_fusedmb
from reduce import Op, Step, Trace

HERE = Path(__file__).resolve().parents[1]
CELL = "v2s.offline.r384.b64"


def load(**changes):
    cfg = json.loads((HERE / "configs" / "efficientnet_v2_s.json").read_text())
    cfg.update(changes)
    return cfg


def test_reference_matches_program_at_small_width():
    """Width 0.25 keeps the published topology: blocks 0-1 have no
    projection, 2-9 expand by 4 and project, 10-39 are MBConv with SE."""
    cfg = load(width_mult=0.25, num_classes=10)
    leaves = work.reference_module(cfg).leaves(cfg)
    assert sorted(leaves["block0"]) == ["conv"]
    assert sorted(leaves["block2"]) == ["conv", "proj"]
    params = weights.make_params(leaves, 7)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    got = np.asarray(run.program_apply(cfg)(params, x))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(run.reference_forward(cfg)(params, x))
    assert got.shape == want.shape == (2, 10)
    assert np.all(np.isfinite(want)) and np.abs(want).max() > 0
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


# EfficientNet-V2-S, arXiv:2104.00298 Table 2, the Fused-MBConv stages at
# 384 (stem output 192): (input side of the stage, expansion, first
# stride, c_in, c_out, repeats); every conv is 3 x 3
FUSED_TABLE = [
    (192, 1, 1, 24, 24, 2),
    (192, 4, 2, 24, 48, 4),
    (96, 4, 2, 48, 64, 4),
]


def fused_macs():
    total = 0
    for res, e, s, c_in, c_out, reps in FUSED_TABLE:
        for i in range(reps):
            ci = c_in if i == 0 else c_out
            side = res // s                 # every block's output side
            cm = c_out if e == 1 else ci * e
            total += side * side * 9 * ci * cm
            if e != 1:
                total += side * side * cm * c_out
    return total


def test_fused_count_matches_the_table_sum():
    lw = work_fusedmb.fused_block_work(load(), 384)
    assert [w.name for w in lw] == [f"fusedmb{i}" for i in range(10)]
    assert sum(w.flops_per_image for w in lw) == 2 * fused_macs()
    assert fused_macs() == 4_515_692_544
    # block 0: 192 x 192 x 24 in and out, a 3 x 3 x 24 x 24 conv and no
    # projection
    assert lw[0].act_bytes_per_image == 4 * 2 * 192 * 192 * 24
    assert lw[0].weight_bytes == 4 * 9 * 24 * 24
    # the network counts of work.py still run on the reference's blocks
    assert work.model_flops_per_image(load(), 384) > 0


HLO = r'''
ENTRY %main.9 (x.1: f32[8,16]) -> f32[8,4] {
  %x.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="x"}
  %stem_fusion = f32[8,16]{1,0} fusion(%x.1), kind=kLoop, calls=%f, metadata={op_name="jit(timed_step)/stem/logistic"}
  %pad.1 = f32[8,128]{1,0} pad(%stem_fusion, %c), metadata={op_name="jit(timed_step)/fusedmb0/jit(_fusedmb_fused)/pad"}
  %fusedmb.2 = f32[8,128]{1,0} custom-call(%pad.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(timed_step)/fusedmb0/jit(_fusedmb_fused)/fusedmb/pallas_call"}
  %add.3 = f32[8,16]{1,0} add(%fusedmb.2, %stem_fusion), metadata={op_name="jit(timed_step)/fusedmb0/add"}
  %mbconv_pass1.4 = f32[8,128]{1,0} custom-call(%add.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(timed_step)/mbconv1/jit(convdk_mbconv_fused)/mbconv_pass1/pallas_call"}
  ROOT %dot.5 = f32[8,4]{1,0} dot(%mbconv_pass1.4), metadata={op_name="jit(timed_step)/head/dot_general"}
}
'''


def made_up(fused=True):
    """Two steps of 100 ns; in each the fused block's pad, kernel and add
    take 10 + 30 + 10 ns.  ``fused=False`` renames the kernel."""
    name = "fusedmb.2" if fused else "other_kernel.2"
    ops = []
    for t0 in (0, 200):
        ops += [Op(t0, t0 + 10, "stem_fusion f32[8,16]", False),
                Op(t0 + 10, t0 + 20, "pad.1 f32[8,128]", False),
                Op(t0 + 20, t0 + 50, f"{name} f32[8,128]", True),
                Op(t0 + 50, t0 + 60, "add.3 f32[8,16]", False),
                Op(t0 + 60, t0 + 90, "mbconv_pass1.4 f32[8,128]", True),
                Op(t0 + 90, t0 + 100, "dot.5 f32[8,4]", False)]
    steps = [Step(0, 100, "jit_timed_step(1)"),
             Step(200, 300, "jit_timed_step(1)")]
    return Trace(ops=[ops], steps=[steps], host_spans=[(0, 400, "window")],
                 window=(0, 400))


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reading(trace):
    cell = run.load_cell(CELL)
    window = run.Window(step_prefixes=("jit_timed_step",),
                        batch=cell.mix["batch"])
    peaks = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return types.SimpleNamespace(cell=cell, window=window, trace=trace,
                                 peaks=peaks)


def test_fusedmb_roofline_reads_the_fused_kernel_time():
    reading = _reading(made_up())
    cell, peaks = reading.cell, reading.peaks
    least = sum(lw.roofline_s(64, peaks["bf16_flops_per_s"],
                              peaks["hbm_bytes_per_s"])
                for lw in work_fusedmb.fused_block_work(cell.cfg, 384))
    # two steps, 30 ns of fusedmb kernel in each
    assert work_fusedmb.kernel_time(reading.trace, ("jit_timed_step",)) \
        == (2, pytest.approx(60e-9))
    got = _reader("fusedmb_roofline.throughput")(reading)
    assert got == pytest.approx(100.0 * 2 * least / 60e-9)
    # nothing to read: no trace, or no fusedmb instruction in it
    assert _reader("fusedmb_roofline.throughput")(_reading(None)) is None
    assert _reader("fusedmb_roofline.throughput")(
        _reading(made_up(fused=False))) is None


def test_fusedmb_share_reads_the_fused_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "timed_step_hlo", lambda cell, batch: HLO)
    read = _reader("fusedmb_share.throughput")
    assert read(_reading(made_up())) == pytest.approx(50.0)
    assert read(_reading(None)) is None
    plain = HLO.replace("/fusedmb0/", "/mbconv0/")
    monkeypatch.setattr(scopes, "timed_step_hlo", lambda cell, batch: plain)
    assert read(_reading(made_up())) is None


def test_cut_down_run_is_correct_and_faults_are_not():
    import tiny
    res = tiny.tiny_run(tiny.tiny_cell(CELL))
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["logit_err"]["value"] < 1e-4
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    bad = tiny.tiny_run(tiny.tiny_cell(CELL), wrap=tiny.answer_altered)
    assert bad["correct"] is False
