"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

One ``Trace`` holds, per device, the operations that ran (with whether
each is a Pallas kernel) and the executions of jitted programs, plus the
host spans the harness wrote.  Everything is on the profiler's one clock,
in nanoseconds.  The functions below are the only arithmetic from trace
to metric: busy time is the union of operation intervals, a step is one
execution of the timed program, and a step's chain interval runs from its
first Pallas event to the end of its last.
"""

from __future__ import annotations

import bisect
import gzip
import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
STEPS_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


@dataclasses.dataclass(frozen=True)
class Op:
    start: int
    end: int
    name: str
    pallas: bool


@dataclasses.dataclass(frozen=True)
class Step:
    start: int
    end: int
    name: str


@dataclasses.dataclass
class Trace:
    ops: List[List[Op]]              # per device, by start
    steps: List[List[Step]]          # per device, by start
    host_spans: List[Tuple[int, int, str]]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def is_pallas(hlo: str) -> bool:
    """A Mosaic kernel: its HLO is a ``tpu_custom_call`` custom call."""
    return 'custom_call_target="tpu_custom_call"' in hlo


def op_name(hlo: str) -> str:
    """``%name = type op(...)`` -> ``name type``: the instruction's name
    and the type it produces."""
    if " = " not in hlo:
        return hlo
    name, rest = hlo.split(" = ", 1)
    return f"{name.lstrip('%')} {rest.split(' ', 1)[0]}"


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one, ``.xplane.pb.gz``).  The
    window is the first host span named ``window_span``, widened to the
    timed programs that overlap it; without one, the extent of the
    device operations."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    return from_profile(data, window_span)


def from_profile(data, window_span: str = WINDOW_SPAN) -> Trace:
    ops: List[List[Op]] = []
    steps: List[List[Step]] = []
    host: List[Tuple[int, int, str]] = []
    devices = sorted((p for p in data.planes
                      if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    for plane in devices:
        d_ops: List[Op] = []
        d_steps: List[Step] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    d_ops.append(Op(s, s + int(ev.duration_ns),
                                    op_name(ev.name), is_pallas(ev.name)))
            elif line.name == STEPS_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    d_steps.append(Step(s, s + int(ev.duration_ns), ev.name))
        ops.append(sorted(d_ops, key=lambda o: o.start))
        steps.append(sorted(d_steps, key=lambda t: t.start))
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                host.append((s, s + int(ev.duration_ns), ev.name))
    window = next(((s, e) for s, e, n in sorted(host) if n == window_span),
                  None)
    if window is not None:
        # host and device clocks agree to a fraction of a millisecond: a
        # program dispatched inside the window belongs to it even where
        # its device events start a little before the host span
        lo, hi = window
        for d in steps:
            for t in d:
                if t.end > lo and t.start < hi:
                    lo, hi = min(lo, t.start), max(hi, t.end)
        window = (lo, hi)
    else:
        all_ops = [o for d in ops for o in d]
        window = (min(o.start for o in all_ops), max(o.end for o in all_ops)) \
            if all_ops else (0, 0)
    return Trace(ops=ops, steps=steps, host_spans=sorted(host), window=window)


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the merged intervals cover."""
    total = 0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran, averaged over
    the devices."""
    if not trace.ops:
        return 0.0
    lo, hi = trace.window
    per = [covered(union((o.start, o.end) for o in d), lo, hi)
           for d in trace.ops]
    return sum(per) / len(per) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    if trace.window_s <= 0 or not any(trace.ops):
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def timed_steps(trace: Trace, prefixes: Sequence[str]) -> List[List[Step]]:
    """Per device, the executions of the timed program inside the window:
    those whose module name starts with one of ``prefixes``."""
    lo, hi = trace.window
    return [[t for t in d if t.start >= lo and t.end <= hi
             and any(t.name.startswith(p) for p in prefixes)]
            for d in trace.steps]


def step_device_s(trace: Trace, prefixes: Sequence[str]) -> float:
    """Seconds of device time of the timed program's executions, summed
    over steps and averaged over devices."""
    per = [sum(t.end - t.start for t in d)
           for d in timed_steps(trace, prefixes)]
    return sum(per) / len(per) * 1e-9 if per else 0.0


@dataclasses.dataclass(frozen=True)
class ChainTime:
    """Busy time inside the steps' chain intervals, split by kind."""

    steps: int           # steps that ran a Pallas kernel
    busy_s: float        # union of all operations
    pallas_s: float      # union of Pallas operations
    other_s: float       # union of the other operations


def chain_time(trace: Trace, prefixes: Sequence[str]) -> ChainTime:
    n, busy, pallas, other = 0, 0, 0, 0
    steps = timed_steps(trace, prefixes)
    for d_ops, d_steps in zip(trace.ops, steps):
        starts = [o.start for o in d_ops]
        for t in d_steps:
            inside = [o for o in d_ops[bisect.bisect_left(starts, t.start):
                                       bisect.bisect_left(starts, t.end)]
                      if o.end <= t.end]
            kern = [o for o in inside if o.pallas]
            if not kern:
                continue
            lo = min(o.start for o in kern)
            hi = max(o.end for o in kern)
            n += 1
            busy += covered(union((o.start, o.end) for o in inside), lo, hi)
            pallas += covered(union((o.start, o.end) for o in kern), lo, hi)
            other += covered(union((o.start, o.end) for o in inside
                                   if not o.pallas), lo, hi)
    k = max(1, len(trace.ops))
    return ChainTime(n, busy / k * 1e-9, pallas / k * 1e-9, other / k * 1e-9)


# -- breakdown ----------------------------------------------------------------

def top_ops(trace: Trace, n: int = 10) -> List[List[object]]:
    """The device operations that took most time in the window, by name
    (seconds summed over every device)."""
    lo, hi = trace.window
    tot: Dict[str, int] = {}
    for d in trace.ops:
        for o in d:
            if o.end > lo and o.start < hi:
                tot[o.name] = tot.get(o.name, 0) + min(o.end, hi) \
                    - max(o.start, lo)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(trace: Trace, n: int = 10) -> List[List[object]]:
    """The longest idle gaps of device 0 inside the window, each named by
    the host span (other than the window) that overlaps it most."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    merged = union((o.start, o.end) for o in trace.ops[0])
    gaps: List[Interval] = []
    cur = lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in trace.host_spans if h[2] != WINDOW_SPAN]
    out = []
    for gs, ge in gaps[:n]:
        best, best_ns = "no host span", 0
        for s, e, name in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ns:
                best, best_ns = name, ov
        out.append([best, (ge - gs) * 1e-9])
    return out
