"""The Fused-MBConv kernel's share of its roofline (%): the least time
the chip could take for the chain's Fused-MBConv blocks (per block,
FLOPs at the bf16 peak or compulsory bytes at HBM bandwidth, whichever
is larger; ``work_fusedmb.py``), over the device time of the ``fusedmb``
Pallas instructions in the timed steps.  None without a trace, or where
no timed step ran a ``fusedmb`` instruction."""

import work_fusedmb


def read(reading):
    t = reading.trace
    if t is None:
        return None
    found = work_fusedmb.kernel_time(t, reading.window.step_prefixes)
    if found is None:
        return None
    steps, seconds = found
    cell, peaks = reading.cell, reading.peaks
    per_step = sum(
        lw.roofline_s(reading.window.batch, peaks["bf16_flops_per_s"],
                      peaks["hbm_bytes_per_s"])
        for lw in work_fusedmb.fused_block_work(cell.cfg,
                                                cell.mix["resolution"]))
    return 100.0 * steps * per_step / seconds
