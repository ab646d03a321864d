"""Peak device memory (MB, 1e6 bytes) on the fullest chip: the larger of
the allocator's ``peak_bytes_in_use`` after the window and what the
window holds at once (every live array, the timed program's temporaries
from the compiler's memory analysis, the outputs in flight;
``run.memory_in_window``)."""


def read(reading):
    b = reading.memory_peak_bytes
    return b / 1e6 if b else None
