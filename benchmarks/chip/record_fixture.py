#!/usr/bin/env python3
"""Records a small traced run of one cell's program, for the trace tests.

    python3 benchmarks/chip/record_fixture.py --workload <cell> \\
        --batch 8 --steps 3 --out benchmarks/chip/fixtures/<name>

Compiles the cell's timed step (``timed_step(p, x)``, as ``run.py`` does)
at ``--batch``, runs it once to warm up, then traces ``--steps`` steps
inside the host spans ``window``, ``dispatch`` and ``block_until_ready``.
Writes ``<out>.xplane.pb.gz`` (the trace) and ``<out>.hlo.txt.gz`` (the
compiled HLO text of the executable that ran).  Needs a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

import run  # puts the benchmark's modules and the program on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import weights
    import work
    cell = run.load_cell(args.workload)
    run.check_device(1)
    cfg, res = cell.cfg, cell.mix["resolution"]
    params = weights.make_params(work.reference_module(cfg).leaves(cfg),
                                 args.seed, np.dtype(cfg["dtype"]))
    x = jax.random.normal(weights.key_for(args.seed, 1),
                          (args.batch, res, res, 3), np.float32)
    apply = run.program_apply(cfg)

    def timed_step(p, x):
        return apply(p, x)
    precision = cfg.get("matmul_precision")
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        step = jax.jit(timed_step).lower(params, x).compile()
    jax.block_until_ready(step(params, x))

    tdir = tempfile.mkdtemp(prefix="chipbench_fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tdir, profiler_options=opts):
        with run.span("window"):
            for _ in range(args.steps):
                with run.span("dispatch"):
                    out = step(params, x)
                with run.span("block_until_ready"):
                    out.block_until_ready()
    found = glob.glob(str(Path(tdir) / "**" / "*.xplane.pb"),
                      recursive=True)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(found[0], "rb") as f, \
            gzip.open(f"{out}.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(f"{out}.hlo.txt.gz", "wt") as g:
        g.write(step.as_text())
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"wrote {out}.xplane.pb.gz and {out}.hlo.txt.gz", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
