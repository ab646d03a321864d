"""The command refuses to run where it cannot measure a TPU."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import run

ROOT = Path(__file__).resolve().parents[3]


def _cmd(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "b0.offline.r224.b256", "--seed", str(2 ** 31 + 5), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cpu_backend_exits_nonzero_without_a_result():
    p = _cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks"
                    / "chip", ignore=shutil.ignore_patterns(".cache"))
    p = _cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_device_kind_missing_from_peaks_is_an_error():
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit) as e:
        run.peaks_for("TPU v99 imaginary")
    assert e.value.code != 0


def test_every_cell_finds_its_files():
    import json
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.limits, w["name"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
        for m in cell.per_layer:
            assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()
