#!/usr/bin/env python3
"""Chip benchmark of the ConvDK vision models: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` at the root of the checkout, its
configuration file, its mix under ``traffic/`` and its limits under
``limits/``; makes the weights and inputs from ``--seed``; warms up every
shape the cell uses (set-up); drives the program for ``--seconds``; then
compares what the timed path produced with the plain float32 reference.
``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics (each read by ``metrics/<name>.py``) instead of the
end-to-end ones.

Needs a TPU: on any other backend, with fewer chips than the cell asks
for, or on a device kind missing from ``peaks.json`` it exits non-zero
and prints no result.  The last line of standard output is one JSON
object; the last lines of standard error are the numbers compared, each
beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = HERE / ".cache"        # compile and schedule caches: a fixed path
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import traffic  # noqa: E402
import work  # noqa: E402


class BenchError(SystemExit):
    """A run that cannot produce a result: exits 2, prints none."""

    def __init__(self, msg: str):
        print(f"run.py: {msg}", file=sys.stderr)
        super().__init__(2)


# -- what the run is ------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                 # the configuration file, as run
    mix: dict                 # the traffic file
    limits: dict              # number compared -> {"limit": ...}
    end_to_end: List[dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str, bench: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    e2e = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return _reports(e2e, cell, bench)


def load_cell(name: str) -> Cell:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    bench = json.loads(path.read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in {path}")
    ce = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.loads((ROOT / ce["file"]).read_text())
    mix = traffic.load(HERE / "traffic" / f"{wl['traffic']}.json")
    lim_path = HERE / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.is_file() else {}
    return Cell(
        name=name, chips=wl["chips"], cfg=cfg, mix=mix, limits=limits,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, name, bench)],
        per_layer=[m for m in bench["per_layer"]
                   if _reports(m, name, bench)])


# -- device, caches, program ---------------------------------------------------

def check_device(chips: int):
    """The TPU devices of this run and their row of ``peaks.json``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {dev.platform!r} "
                         f"({dev.device_kind})")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips], peaks_for(dev.device_kind)


def peaks_for(kind: str) -> dict:
    """The row of ``peaks.json`` for a device kind; a kind that is not
    there is an error, never a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def enable_caches() -> None:
    import jax
    from repro.core.autotune import set_schedule_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    set_schedule_cache_dir(CACHE / "schedules")


def program_config(cfg: dict):
    """The program's configuration object, built from the file's keys."""
    mod = importlib.import_module(cfg["program"]["module"])
    cls = getattr(mod, cfg["program"]["config"])
    kw = {}
    for f in dataclasses.fields(cls):
        if f.name in cfg:
            v = cfg[f.name]
            kw[f.name] = tuple(tuple(r) for r in v) if isinstance(v, list) \
                else v
    return cls(**kw)


def program_apply(cfg: dict) -> Callable:
    """``apply(params, images)`` of the configuration's served entry."""
    mod = importlib.import_module(cfg["program"]["module"])
    entry = getattr(mod, cfg["program"]["apply"])
    pcfg = program_config(cfg)

    def apply(params, images):
        return entry(params, images, pcfg)
    return apply


def reference_forward(cfg: dict) -> Callable:
    ref = work.reference_module(cfg)
    return lambda params, images: ref.forward(params, images, cfg)


class CompileCounter:
    """Counts compilations (and compile-cache reads) while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, *_a, **_k) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)


# -- what a window saw --------------------------------------------------------

@dataclasses.dataclass
class Window:
    attempted: int = 0
    failed: int = 0
    images: int = 0                 # real images completed
    seconds: float = 0.0            # first dispatch -> last ready
    model_flops: float = 0.0        # of the real images completed
    step_prefixes: tuple = ()       # module names of the timed program
    batch: int = 0                  # images per launch of the timed step
    compiles: int = 0
    setup_s: float = 0.0            # process start -> first timed batch
    memory_bytes: int = 0           # see memory_in_window
    # (image (R, R, 3) float32, logits) sampled for the comparison with
    # the reference
    samples: List[tuple] = dataclasses.field(default_factory=list)


def phase(name: str) -> None:
    """One line on standard error: seconds since the process started."""
    print(f"run.py: {time.perf_counter() - T_START:8.2f} s  {name}",
          file=sys.stderr, flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_in_window(step, inflight: int) -> int:
    """Device bytes the window holds at once: every array live on the
    device as it opens, the timed program's temporaries (the compiler's
    memory analysis of it) and the outputs of the steps in flight.  The
    allocator's ``peak_bytes_in_use`` may leave the temporaries out; the
    run reports the larger of the two (``memory_peak``)."""
    import jax
    ma = step.memory_analysis()
    live = sum(a.nbytes for a in jax.live_arrays())
    return int(live + ma.temp_size_in_bytes
               + inflight * ma.output_size_in_bytes)


def _sample_rows(n: int, k: int, seed: int) -> List[int]:
    return sorted(int(i) for i in traffic.rng_for(seed, 3).permutation(n)[:k])


# -- the traffic ----------------------------------------------------------------

def run_offline(cell: Cell, params, apply, seed: int, seconds: float,
                counter: CompileCounter, trace_ctx, wrap=None) -> Window:
    """Back-to-back batches, two in flight; a batch counts once its
    logits are ready."""
    import jax
    import weights
    mix, res = cell.mix, cell.mix["resolution"]
    b, n = mix["batch"], mix["distinct_batches"]
    gen = jax.jit(lambda key: tuple(
        jax.random.normal(k, (b, res, res, 3), np.float32)
        for k in jax.random.split(key, n)))
    batches = jax.block_until_ready(gen(weights.key_for(seed, 1)))
    phase("inputs on the device")
    fn = wrap(apply) if wrap else apply

    def timed_step(p, x):
        return fn(p, x)
    # compiled ahead of the window, so its memory analysis is the timed
    # program's own
    step = jax.jit(timed_step).lower(params, batches[0]).compile()
    phase("timed step traced, solved, compiled (or read from the cache)")
    for k, x in enumerate(batches[:2]):
        jax.block_until_ready(step(params, x))
        phase(f"warm-up step {k}")
    setup_s = time.perf_counter() - T_START

    w = Window(step_prefixes=("jit_timed_step",))
    w.memory_bytes = memory_in_window(step, inflight=2)
    last: Dict[int, object] = {}
    inflight: deque = deque()
    counter.on = True
    with trace_ctx():
        with span("window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            i, t_end = 0, t0
            while True:
                with span("dispatch"):
                    inflight.append((i, step(params, batches[i % n])))
                i += 1
                if len(inflight) < 2 and time.perf_counter() < deadline:
                    continue
                j, out = inflight.popleft()
                with span("block_until_ready"):
                    out.block_until_ready()
                t_end = time.perf_counter()
                last[j % n] = out
                w.images += b
                if t_end >= deadline:
                    break
            while inflight:
                j, out = inflight.popleft()
                with span("block_until_ready"):
                    out.block_until_ready()
                t_end = time.perf_counter()
                last[j % n] = out
                w.images += b
    counter.on = False
    w.compiles = counter.count
    w.seconds = t_end - t0
    w.attempted = i * b
    w.failed = w.attempted - w.images
    w.model_flops = w.images * work.model_flops_per_image(cell.cfg, res)
    w.batch = b
    # the comparison's sample: rows drawn from the seed across the
    # distinct batches, from the logits the window produced
    host = [np.asarray(batches[k]) for k in sorted(last)]
    logits = [np.asarray(last[k]) for k in sorted(last)]
    rows = _sample_rows(len(host) * b, mix["compare"], seed)
    for r in rows:
        w.samples.append((host[r // b][r % b], logits[r // b][r % b]))
    w.setup_s = setup_s
    return w


# -- the comparison with the reference ----------------------------------------

def compare(samples, params, cfg: dict, chunk: int = 16
            ) -> Dict[str, float]:
    """Runs the float32 reference (at ``highest`` matmul precision) over
    every sampled image, in blocks of ``chunk``, and returns the numbers
    compared:

    * ``logit_err``: the widest gap between a served logit and the
      reference's, as a share of that image's largest reference logit,
      over the sample;
    * ``nonfinite``: served logits that are not finite numbers.
    """
    import jax
    fwd = jax.jit(reference_forward(cfg))
    worst, nonfinite = 0.0, 0
    for s in range(0, len(samples), chunk):
        part = samples[s:s + chunk]
        x = np.zeros((chunk,) + part[0][0].shape, np.float32)
        for j, (img, _) in enumerate(part):
            x[j] = img
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fwd(params, x), np.float64)
        for j, (_, got) in enumerate(part):
            got = np.asarray(got, np.float64)
            if not np.all(np.isfinite(got)):
                nonfinite += 1
                continue
            scale = np.abs(want[j]).max()
            worst = max(worst, float(np.abs(got - want[j]).max() / scale))
    return {"logit_err": worst, "nonfinite": float(nonfinite)}


def judge(numbers: Dict[str, float], limits: dict, n_samples: int):
    """(correct, checks) — every number at or under its limit."""
    checks = {"samples": {"value": n_samples, "limit": 1}}
    ok = n_samples >= 1
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit", 0.0)
        checks[name] = {"value": value, "limit": lim}
        ok = ok and value <= lim
    return ok, checks


# -- per-layer metrics ----------------------------------------------------------

@dataclasses.dataclass
class Reading:
    """What one run saw, handed to each per-layer metric's reader."""

    cell: Cell
    window: Window
    peaks: dict
    chips: int
    memory_peak_bytes: int
    trace: object = None            # reduce.Trace of a --trace 1 run


def read_metric(name: str, reading: Reading) -> Optional[float]:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(reading)
    return None if value is None else float(value)


def end_to_end(cell: Cell, w: Window) -> Dict[str, float]:
    return {"setup_s": w.setup_s, "images_per_s": w.images / w.seconds}


# -- one run ------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def memory_peak(devices, w: Window) -> int:
    """Peak device bytes on the fullest chip: the allocator's peak after
    the window, or what the window holds at once, whichever is larger."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(max(peaks), w.memory_bytes))


def run(args, *, cell: Optional[Cell] = None, devices=None, peaks=None,
        wrap=None) -> dict:
    """One run of one cell; returns the result object.  ``devices`` and
    ``peaks`` skip the look for a chip (tests); ``wrap`` replaces the
    timed entry ``apply`` by ``wrap(apply)`` (the control and the fault
    tests)."""
    import contextlib
    import jax
    import weights
    cell = cell or load_cell(args.workload)
    if devices is None:
        devices, peaks = check_device(cell.chips)
        enable_caches()
        phase(f"{len(devices)} x {devices[0].device_kind}")
    counter = CompileCounter()
    params = jax.block_until_ready(weights.make_params(
        work.reference_module(cell.cfg).leaves(cell.cfg), args.seed,
        np.dtype(cell.cfg["dtype"])))
    phase("weights on the device")
    apply = program_apply(cell.cfg)

    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

        @contextlib.contextmanager
        def trace_ctx():
            with jax.profiler.trace(tdir, profiler_options=opts):
                yield
    else:
        trace_ctx = contextlib.nullcontext
    precision = cell.cfg.get("matmul_precision")
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        w = run_offline(cell, params, apply, args.seed, args.seconds,
                        counter, trace_ctx, wrap)
    counter.close()
    mem = memory_peak(devices, w)

    trace = None
    if tdir is not None:
        import reduce
        found = glob.glob(str(Path(tdir) / "**" / "*.xplane.pb"),
                          recursive=True)
        trace = reduce.load(found[0]) if found else None
        shutil.rmtree(tdir, ignore_errors=True)
    reading = Reading(cell=cell, window=w, peaks=peaks, chips=cell.chips,
                      memory_peak_bytes=mem, trace=trace)

    phase("window closed")
    numbers = compare(w.samples, params, cell.cfg)
    phase("reference compared")
    correct, checks = judge(numbers, cell.limits, len(w.samples))
    checks["compiles_in_window"] = {"value": w.compiles, "limit": 0}
    correct = correct and w.compiles == 0

    metrics: Dict[str, dict] = {}
    if args.trace:
        for m in cell.per_layer:
            v = read_metric(m["name"], reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(cell, w)
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": int(w.attempted),
              "failed": int(w.failed), "metrics": metrics, "device": device}
    if trace is not None:
        import reduce
        device["busy_s"] = reduce.busy_s(trace)
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": reduce.top_ops(trace),
                               "idle_gaps": reduce.idle_gaps(trace)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = run(args)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
