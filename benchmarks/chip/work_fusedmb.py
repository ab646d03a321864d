"""Work counts of a configuration's Fused-MBConv blocks, from its spec
table alone, and the device time of the ``fusedmb`` kernel in a trace.

A Fused-MBConv block is one dense k x k / s convolution (``c_in`` ->
``c_mid``) and, when it expands, a 1x1 projection (``c_mid`` ->
``c_out``); at expansion 1 the convolution goes straight to ``c_out``.
FLOPs count two per multiply-add of both at logical widths (the
activation and the residual add are left out).  Compulsory HBM bytes are
the block's input, its weights and its output, each moved once, at the
configuration's dtype: the expanded tensor never needs to leave the chip.
Nothing here reads the program's traffic model.
"""

from __future__ import annotations

import bisect
import re
from typing import List, Optional

import numpy as np

import reduce
from work import LayerWork, reference_module

KERNEL = "fusedmb"        # the ``name=`` of the fused kernel's pallas_call
_INSTRUCTION = re.compile(rf"{KERNEL}(\.\d+)?")


def _same(n: int, s: int) -> int:
    return -(-n // s)


def fused_block_work(cfg: dict, resolution: int) -> List[LayerWork]:
    """One ``LayerWork`` per Fused-MBConv block of the chain, named
    ``fusedmb<i>`` by its place in the chain."""
    dt = np.dtype(cfg["dtype"]).itemsize
    h = _same(resolution, 2)
    out = []
    for i, b in enumerate(reference_module(cfg).blocks(cfg)):
        ho = _same(h, b.s)
        if getattr(b, "projects", None) is not None:
            macs = ho * ho * b.k * b.k * b.c_in * b.c_mid
            weights = b.k * b.k * b.c_in * b.c_mid
            if b.projects:
                macs += ho * ho * b.c_mid * b.c_out
                weights += b.c_mid * b.c_out
            out.append(LayerWork(
                f"fusedmb{i}", 2.0 * macs,
                dt * (h * h * b.c_in + ho * ho * b.c_out), dt * weights))
        h = ho
    return out


def kernel_time(trace: reduce.Trace, prefixes) -> Optional[tuple]:
    """(steps, seconds): the timed steps that ran a ``fusedmb`` Pallas
    instruction, and the union of those instructions' device time inside
    them, averaged over devices.  None where no step ran one."""
    steps = reduce.timed_steps(trace, prefixes)
    n, ns = 0, 0
    for d_ops, d_steps in zip(trace.ops, steps):
        starts = [o.start for o in d_ops]
        for t in d_steps:
            iv = [(o.start, o.end) for o in
                  d_ops[bisect.bisect_left(starts, t.start):
                        bisect.bisect_left(starts, t.end)]
                  if o.pallas
                  and _INSTRUCTION.fullmatch(o.name.split(" ", 1)[0])]
            if iv:
                n += 1
                ns += reduce.covered(reduce.union(iv), t.start, t.end)
    if n == 0 or ns <= 0:
        return None
    k = max(1, len(trace.ops))
    return n / k, ns / k * 1e-9
