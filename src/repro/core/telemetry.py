"""Structured telemetry for the ConvDK stack: counters, spans, and the one
canonical ``measure()`` timing harness.

Every schedule decision in this repo is solved from modeled byte counts;
this module is where the *measured* side of the story lives, plus the
counters that let a run explain what it actually did:

* **Counters** — monotonically increasing named totals (bytes modeled, DMA
  issues, collective words, schedule-cache hits/misses/migrations, solver
  decisions).  Incrementing is a dict update behind a lock: cheap enough
  to leave permanently on.
* **Spans** — named wall-time aggregates (count / total / min / max) via
  the ``span(name)`` context manager.  Each span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, so under a profiler
  trace it lands on the ``/host:CPU`` plane, on the device ops' clock.
  A span re-entered under itself on one thread (the plan solvers nest)
  is timed and annotated once, at its outermost entry.
* **Series** — bounded sample recorders (``record(name, value)``) for
  distributions the aggregates cannot answer: request latencies, queue
  waits.  A series keeps the most recent ``SERIES_CAP`` samples and
  summarizes as count / last / max / nearest-rank percentiles
  (``percentiles()``) — the serving layer's p50/p90/p99 live here.
* **``measure()``** — THE timing loop for real kernel executions: warmup
  calls (compile) followed by timed iterations, each blocked to
  completion with ``jax.block_until_ready`` (which walks pytrees, so
  tuple-returning benchmarks no longer need — and no longer get — the
  call-it-twice probe the old ad-hoc loops used).  ``benchmarks/run.py``,
  ``benchmarks/kernel_bench.py`` and ``core.autotune``'s measured sweeps
  all route through it.

**Jit semantics** (pinned by ``tests/test_telemetry.py``): counters are
host-side Python state, so an increment placed inside a jitted function
fires at TRACE time — once per compilation, not once per call.  That is
the honest semantic for the hooks this repo installs (staging plans,
sharded dispatches, schedule solves are all trace-time work); anything
that must tick per execution belongs in the caller, around the call.

The global registry is process-wide.  ``snapshot()`` returns plain dicts
(JSON-ready, the form ``BENCH_<host>.json`` artifacts embed);
``reset()`` zeroes it (tests).
"""

from __future__ import annotations

import math
import os
import platform
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

__all__ = [
    "Measurement",
    "SERIES_CAP",
    "SpanStat",
    "Telemetry",
    "counter",
    "get_telemetry",
    "host_fingerprint",
    "host_slug",
    "measure",
    "percentiles",
    "record",
    "reset",
    "series",
    "snapshot",
    "span",
]

# samples kept per series (most recent win): enough for stable p99 at
# serving smoke scale without unbounded growth on a long-lived engine
SERIES_CAP = 4096


def percentiles(values: Sequence[float],
                qs: Sequence[float] = (50, 90, 99)) -> Dict[str, float]:
    """Nearest-rank percentiles of ``values`` as ``{"p50": ...}``.

    Nearest-rank (ceil(q/100 * n)-th order statistic) rather than
    interpolation: every reported number is a latency that actually
    happened, which is the honest form for small serving samples.
    Empty input -> empty dict."""
    if not values:
        return {}
    ordered = sorted(values)
    n = len(ordered)
    out = {}
    for q in qs:
        rank = min(n, max(1, math.ceil(q / 100.0 * n)))
        out[f"p{q:g}"] = ordered[rank - 1]
    return out


@dataclass
class SpanStat:
    """Aggregate wall-time of one named span."""

    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.min_s = min(self.min_s, seconds)
        self.max_s = max(self.max_s, seconds)

    def as_dict(self) -> Dict[str, float]:
        return {"count": self.count, "total_s": self.total_s,
                "min_s": self.min_s if self.count else 0.0,
                "max_s": self.max_s}


class Telemetry:
    """A counter + span registry.  One process-wide instance lives in this
    module; tests may construct private ones."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._counters: Dict[str, float] = {}
        self._spans: Dict[str, SpanStat] = {}
        self._series: Dict[str, deque] = {}
        self._local = threading.local()     # per-thread span depths

    # -- counters ------------------------------------------------------------

    def count(self, name: str, value: float = 1) -> None:
        """Add ``value`` to counter ``name`` (created at 0)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def get(self, name: str, default: float = 0) -> float:
        with self._lock:
            return self._counters.get(name, default)

    # -- spans ---------------------------------------------------------------

    @contextmanager
    def span(self, name: str):
        """Time a ``with`` block into the span aggregate ``name`` and mark
        it on the profiler's host timeline.  Only the outermost entry of
        ``name`` on this thread counts."""
        depth = self._local.__dict__.setdefault("depth", {})
        outer = depth.get(name, 0)
        depth[name] = outer + 1
        if outer:
            try:
                yield self
            finally:
                depth[name] = outer
            return
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield self
        finally:
            dt = time.perf_counter() - t0
            depth[name] = outer
            with self._lock:
                self._spans.setdefault(name, SpanStat()).add(dt)

    def span_stat(self, name: str) -> Optional[SpanStat]:
        with self._lock:
            return self._spans.get(name)

    # -- series --------------------------------------------------------------

    def record(self, name: str, value: float) -> None:
        """Append one sample to series ``name`` (bounded to SERIES_CAP)."""
        with self._lock:
            self._series.setdefault(
                name, deque(maxlen=SERIES_CAP)).append(float(value))

    def series(self, name: str) -> Tuple[float, ...]:
        """The retained samples of series ``name`` (oldest first)."""
        with self._lock:
            return tuple(self._series.get(name, ()))

    # -- registry ------------------------------------------------------------

    def snapshot(self) -> Dict[str, dict]:
        """JSON-ready view:
        ``{"counters": {...}, "spans": {...}, "series": {...}}`` — series
        summarize to count/last/max plus nearest-rank p50/p90/p99."""
        with self._lock:
            series = {}
            for k in sorted(self._series):
                vals = self._series[k]
                series[k] = {"count": len(vals), "last": vals[-1],
                             "max": max(vals), **percentiles(vals)}
            return {
                "counters": dict(sorted(self._counters.items())),
                "spans": {k: v.as_dict()
                          for k, v in sorted(self._spans.items())},
                "series": series,
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._spans.clear()
            self._series.clear()


_GLOBAL = Telemetry()


def get_telemetry() -> Telemetry:
    return _GLOBAL


def counter(name: str, value: float = 1) -> None:
    """Increment a global counter (module-level sugar)."""
    _GLOBAL.count(name, value)


def span(name: str):
    """Global span context manager (module-level sugar)."""
    return _GLOBAL.span(name)


def record(name: str, value: float) -> None:
    """Append one sample to a global series (module-level sugar)."""
    _GLOBAL.record(name, value)


def series(name: str) -> Tuple[float, ...]:
    """Retained samples of a global series (module-level sugar)."""
    return _GLOBAL.series(name)


def snapshot() -> Dict[str, dict]:
    return _GLOBAL.snapshot()


def reset() -> None:
    _GLOBAL.reset()


# ---------------------------------------------------------------------------
# the canonical timing harness
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Measurement:
    """Result of one ``measure()`` run: the timed iterations, in order."""

    name: Optional[str]
    times_s: Tuple[float, ...]

    @property
    def iters(self) -> int:
        return len(self.times_s)

    @property
    def best_s(self) -> float:
        """Fastest iteration — the least-noise estimate of the kernel."""
        return min(self.times_s)

    @property
    def mean_s(self) -> float:
        return sum(self.times_s) / len(self.times_s)

    @property
    def best_us(self) -> float:
        return self.best_s * 1e6

    @property
    def mean_us(self) -> float:
        return self.mean_s * 1e6


def measure(fn: Callable, *args, iters: int = 5, warmup: int = 1,
            name: Optional[str] = None, **kwargs) -> Measurement:
    """Time ``fn(*args, **kwargs)``: ``warmup`` untimed calls (compile /
    cache fill), then ``iters`` timed calls, each blocked to completion.

    ``jax.block_until_ready`` walks arbitrary pytrees (tuples included)
    and passes non-arrays through, so this one loop serves jax kernels,
    tuple-returning sweeps and plain-Python table builders alike — no
    per-call-site probing of the return type, and never an extra
    evaluation to decide how to block (the bug the old ad-hoc loops had).

    With ``name`` the total wall time (warmup included) is also recorded
    as the global span ``measure.<name>``.
    """
    if iters < 1:
        raise ValueError(f"measure() needs iters >= 1, got {iters}")
    import jax

    ctx = _GLOBAL.span(f"measure.{name}") if name else None
    try:
        if ctx is not None:
            ctx.__enter__()
        for _ in range(max(0, warmup)):
            jax.block_until_ready(fn(*args, **kwargs))
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, **kwargs))
            times.append(time.perf_counter() - t0)
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    return Measurement(name=name, times_s=tuple(times))


# ---------------------------------------------------------------------------
# host identity (BENCH_<host>.json artifacts)
# ---------------------------------------------------------------------------


def host_fingerprint() -> Dict[str, object]:
    """Where a measurement ran: the fields two BENCH artifacts must share
    for their wall times to be comparable (the trajectory differ enforces
    byte/axis fields regardless — those are host-independent)."""
    try:
        import jax
        jax_version = jax.__version__
        backend = jax.default_backend()
    except Exception:  # pragma: no cover - jax is always importable here
        jax_version, backend = "unknown", "unknown"
    return {
        "node": platform.node() or "unknown",
        "system": platform.system(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax": jax_version,
        "backend": backend,
        "cpu_count": os.cpu_count() or 0,
    }


def host_slug(fingerprint: Optional[Dict[str, object]] = None) -> str:
    """Filesystem-safe host tag for ``BENCH_<host>.json`` filenames."""
    fp = fingerprint or host_fingerprint()
    raw = f"{fp.get('node', 'unknown')}-{fp.get('backend', 'unknown')}"
    slug = re.sub(r"[^A-Za-z0-9_.-]+", "-", str(raw))
    slug = re.sub(r"-{2,}", "-", slug).strip("-")
    return slug or "unknown"
