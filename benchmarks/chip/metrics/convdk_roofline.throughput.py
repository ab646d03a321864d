"""The MBConv chain's share of its roofline (%): the blocks' least
possible time over the device time from each step's first Pallas kernel
to the end of its last, so XLA work between kernels counts against it."""

from metrics.shared import chain_roofline


def read(reading):
    return chain_roofline(reading)
