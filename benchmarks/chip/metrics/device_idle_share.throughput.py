"""Share of the traced window (%) in which no operation ran on the
device: 1 - busy union / window."""

from metrics.shared import idle_share


def read(reading):
    return idle_share(reading)
