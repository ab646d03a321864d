"""Arithmetic the per-layer metric readers share.

Each reader (``metrics/<metric>.py``) defines ``read(reading)`` and
returns a number, or None where the run has nothing for it to read; a
share of a peak or a roofline is never returned as 0 for want of data.
``reading`` is ``run.Reading``: the cell, the window's records, the
device's row of ``peaks.json``, the chips, the peak memory and, in a
``--trace 1`` run, the reduced trace.
"""

from __future__ import annotations

from typing import Optional

import reduce
import work


def mfu(reading) -> Optional[float]:
    """Model FLOPs of the real images completed in the window over the
    timed program's device time x chips x the bf16 peak, in %."""
    t = reading.trace
    if t is None:
        return None
    step_s = reduce.step_device_s(t, reading.window.step_prefixes)
    if step_s <= 0 or reading.window.model_flops <= 0:
        return None
    return 100.0 * reading.window.model_flops / (
        step_s * reading.chips * reading.peaks["bf16_flops_per_s"])


def chain(reading) -> Optional[reduce.ChainTime]:
    t = reading.trace
    if t is None:
        return None
    ct = reduce.chain_time(t, reading.window.step_prefixes)
    return ct if ct.steps and ct.busy_s > 0 else None


def chain_roofline(reading) -> Optional[float]:
    """Sum over the chain's blocks of the least time the chip could take
    (FLOPs at the bf16 peak or compulsory bytes at HBM bandwidth,
    whichever is larger) over the chain's device time, in %."""
    ct = chain(reading)
    if ct is None:
        return None
    cell, peaks = reading.cell, reading.peaks
    batch = reading.window.batch
    per_step = sum(
        lw.roofline_s(batch, peaks["bf16_flops_per_s"],
                      peaks["hbm_bytes_per_s"])
        for lw in work.block_work(cell.cfg, cell.mix["resolution"]))
    return 100.0 * ct.steps * per_step / ct.busy_s


def idle_share(reading) -> Optional[float]:
    t = reading.trace
    if t is None:
        return None
    v = reduce.idle_share(t)
    return None if v is None else 100.0 * v
