"""Share (%) of the chain's device time spent in operations that are not
Pallas kernels: pads, slices, residual adds and SE ops between blocks."""

from metrics.shared import chain


def read(reading):
    ct = chain(reading)
    return None if ct is None else 100.0 * ct.other_s / ct.busy_s
