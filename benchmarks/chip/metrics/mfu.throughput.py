"""The whole step's share of the chip's bf16 peak (%), from the device
time of the timed program's executions in the trace."""

from metrics.shared import mfu


def read(reading):
    return mfu(reading)
