#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,13 [--control-seeds 11,12,13] --seconds 3 \\
        [--out <file.json>]

In one process, runs the cell as ``run.py`` does (a short window at the
cell's own load and sizes) once per seed with the program, and once per
control seed with the control in the program's place.  The control is the plain
reference computed in the nearest precision below the one the
configuration states: the configurations state float32 at
``"matmul_precision": "highest"``, so every contraction runs in three
bfloat16 passes (``Precision.HIGH``, written out in
``reference.common.contract``).  Prints one JSON object per
run with the numbers compared; the lower reading of a limit is the
largest the program gives over its seeds, the upper the smallest the
control gives.

Not part of a benchmark run: ``run.py`` never runs the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def control_wrap(cfg: dict):
    """``wrap`` for ``run.run``: the control in the place of the timed
    entry."""
    if cfg.get("matmul_precision") != "highest":
        raise ValueError("the control is written for float32 at "
                         "matmul_precision 'highest'")
    ref = run.work.reference_module(cfg)

    def wrap(_apply):
        def control(params, images):
            return ref.forward(params, images, cfg, passes=3)
        return control
    return wrap


def readings(workload: str, seeds, control_seeds, seconds: float):
    """Yields one record per run."""
    cell = run.load_cell(workload)
    devices, peaks = run.check_device(cell.chips)
    run.enable_caches()
    runs = [(s, "program", None) for s in seeds] + \
        [(s, "control", control_wrap(cell.cfg)) for s in control_seeds]
    for seed, side, wrap in runs:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0)
        res = run.run(args, cell=cell, devices=devices, peaks=peaks,
                      wrap=wrap)
        yield {"workload": workload, "seed": seed, "side": side,
               "correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"],
               "checks": {k: v["value"] for k, v in res["checks"].items()},
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control-seeds", default="",
                    help="comma-separated; none by default")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    control_seeds = [int(s) for s in a.control_seeds.split(",") if s]
    out = []
    for rec in readings(a.workload, seeds, control_seeds, a.seconds):
        print(json.dumps(rec), flush=True)
        out.append(rec)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
